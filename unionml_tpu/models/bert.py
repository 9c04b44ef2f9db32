"""BERT encoder family (flax) — the flagship model for the BERT-base fine-tune target.

Built TPU-first rather than ported: bfloat16 compute with f32 params/logits, the
framework's flash-attention kernel (:mod:`unionml_tpu.ops.attention`) behind every
layer, optional remat (``jax.checkpoint``) on encoder layers to trade FLOPs for HBM,
and a logical-axis sharding map (``param_shardings``) covering data/FSDP/tensor
parallelism so the same module runs single-chip or pjit-sharded over a mesh.

HF-compatible: ``import_hf_weights`` maps a ``transformers`` BERT state dict onto this
module's parameter tree (validated numerically against torch in tests).

Reference context: the reference has no model zoo at all — its BERT story is "user
brings a HF Trainer inside @model.trainer" (``templates/quickdraw``-style); here the
framework owns the model + train step so the TPU path is compiled end-to-end
(BASELINE.json north star).
"""

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from unionml_tpu.ops.attention import attention
from unionml_tpu.parallel.mesh import DATA_AXIS, FSDP_AXIS, TENSOR_AXIS


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    num_labels: int = 2
    dtype: Any = jnp.bfloat16
    #: "auto" | "xla" | "pallas" | "ring" | "ulysses" — the last two are the
    #: sequence-parallel long-context paths and require ``sp_mesh``
    attention_impl: str = "auto"
    #: mesh carrying a "sequence" axis for ring/ulysses attention
    sp_mesh: Any = None
    remat: bool = False
    #: tanh-approximate GELU trades exact erf (VPU-expensive) for the cheaper tanh
    #: polynomial — numerically within ~1e-3 of exact, a candidate MFU lever that
    #: has not been measured on the chip, so the default stays exact
    gelu_approximate: bool = False

    @classmethod
    def base(cls, **overrides) -> "BertConfig":
        return cls(**overrides)

    @classmethod
    def tiny(cls, **overrides) -> "BertConfig":
        """A 2-layer config for tests and multi-chip dry runs."""
        defaults = dict(
            vocab_size=1024,
            hidden_size=128,
            num_layers=2,
            num_heads=4,
            intermediate_size=256,
            max_position_embeddings=128,
        )
        defaults.update(overrides)
        return cls(**defaults)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


class BertSelfAttention(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, hidden, attn_inputs, deterministic: bool):
        cfg = self.config
        dense = lambda name: nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name=name)
        q = dense("query")(hidden)
        k = dense("key")(hidden)
        v = dense("value")(hidden)

        batch, seq, _ = hidden.shape
        kv_lens, dense_mask = attn_inputs
        split = lambda x: x.reshape(batch, seq, cfg.num_heads, cfg.head_dim).transpose(0, 2, 1, 3)
        if cfg.attention_impl in ("ring", "ulysses"):
            # sequence-parallel long-context path: activations shard over the mesh's
            # "sequence" axis; padding arrives as per-batch kv_lens (right padding)
            from unionml_tpu.parallel import sp_attention

            context = sp_attention(
                split(q), split(k), split(v), cfg.sp_mesh, cfg.attention_impl, kv_lens=kv_lens
            )
        else:
            context = attention(
                split(q), split(k), split(v), mask=dense_mask, kv_lens=kv_lens, impl=cfg.attention_impl
            )
        context = context.transpose(0, 2, 1, 3).reshape(batch, seq, cfg.hidden_size)

        out = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="output")(context)
        out = nn.Dropout(cfg.hidden_dropout)(out, deterministic=deterministic)
        return nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="output_norm")(
            out + hidden
        )


class BertMlp(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, hidden, deterministic: bool):
        cfg = self.config
        up = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype, name="intermediate")(hidden)
        up = nn.gelu(up, approximate=cfg.gelu_approximate)
        down = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="output")(up)
        down = nn.Dropout(cfg.hidden_dropout)(down, deterministic=deterministic)
        return nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="output_norm")(
            down + hidden
        )


class BertLayer(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, hidden, attn_inputs, deterministic: bool):
        hidden = BertSelfAttention(self.config, name="attention")(hidden, attn_inputs, deterministic)
        return BertMlp(self.config, name="mlp")(hidden, deterministic)


class BertEncoder(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, hidden, attn_inputs, deterministic: bool):
        layer_cls = BertLayer
        if self.config.remat:
            layer_cls = nn.remat(BertLayer, static_argnums=(3,))
        for i in range(self.config.num_layers):
            hidden = layer_cls(self.config, name=f"layer_{i}")(hidden, attn_inputs, deterministic)
        return hidden


class BertModel(nn.Module):
    """Embeddings + encoder + pooler (tanh over [CLS])."""

    config: BertConfig

    @nn.compact
    def __call__(
        self,
        input_ids,
        attention_mask=None,
        token_type_ids=None,
        deterministic: bool = True,
    ):
        cfg = self.config
        batch, seq = input_ids.shape
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        # the flash kernel consumes per-batch valid lengths, which is exact only for
        # contiguous right-padding (the HF default); whenever the XLA impl is what
        # actually runs (explicitly or via "auto" off-TPU) it gets the full dense mask
        # so left-padded / arbitrary masks stay exact
        kv_lens = None
        dense_mask = None
        if attention_mask is not None:
            resolved_impl = cfg.attention_impl
            if resolved_impl == "auto":
                from unionml_tpu.ops.attention import on_tpu

                resolved_impl = "pallas" if on_tpu() else "xla"
            if resolved_impl == "xla":
                dense_mask = attention_mask[:, None, None, :].astype(bool)
            else:
                # pallas / ring / ulysses consume per-batch lengths (right padding);
                # the sp entrypoints default missing kv_lens to full length themselves
                kv_lens = jnp.sum(attention_mask.astype(jnp.int32), axis=-1)

        word = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="word_embeddings")(
            input_ids
        )
        position = nn.Embed(
            cfg.max_position_embeddings, cfg.hidden_size, dtype=cfg.dtype, name="position_embeddings"
        )(jnp.arange(seq)[None, :])
        token_type = nn.Embed(
            cfg.type_vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="token_type_embeddings"
        )(token_type_ids)

        hidden = word + position + token_type
        hidden = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype, name="embeddings_norm")(hidden)
        hidden = nn.Dropout(cfg.hidden_dropout)(hidden, deterministic=deterministic)

        hidden = BertEncoder(cfg, name="encoder")(hidden, (kv_lens, dense_mask), deterministic)

        pooled = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="pooler")(hidden[:, 0])
        pooled = jnp.tanh(pooled)
        return hidden, pooled


class BertForSequenceClassification(nn.Module):
    """BERT + classification head — the fine-tune target model."""

    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None, deterministic: bool = True):
        _, pooled = BertModel(self.config, name="bert")(
            input_ids, attention_mask, token_type_ids, deterministic
        )
        pooled = nn.Dropout(self.config.hidden_dropout)(pooled, deterministic=deterministic)
        # classification logits in f32: cheap, and keeps the loss numerically exact
        return nn.Dense(self.config.num_labels, dtype=jnp.float32, name="classifier")(pooled)


# ---------------------------------------------------------------------- shardings

def param_shardings(params: Any, mesh_axis_names: Tuple[str, ...] = (DATA_AXIS, TENSOR_AXIS)) -> Any:
    """PartitionSpec tree for the BERT parameter pytree.

    Layout (the standard Megatron-style split expressed as jax shardings):

    - attention q/k/v kernels: shard output dim (heads) over ``tensor``
    - attention output kernel: shard input dim over ``tensor``
    - MLP up-projection: shard output dim over ``tensor``; down-projection: input dim
    - embeddings: shard vocab dim over ``tensor``
    - everything else replicated (or FSDP-sharded over ``fsdp`` when that axis exists)

    XLA inserts the matching all-reduces over ICI; nothing else is needed.
    """
    from jax.sharding import PartitionSpec as P

    has_tensor = TENSOR_AXIS in mesh_axis_names
    has_fsdp = FSDP_AXIS in mesh_axis_names
    tensor = TENSOR_AXIS if has_tensor else None
    fsdp = FSDP_AXIS if has_fsdp else None

    def spec_for(path: Tuple[str, ...], leaf) -> P:
        path_str = "/".join(str(p) for p in path)
        ndim = getattr(leaf, "ndim", 0)
        if ndim < 2:
            return P()
        if "embeddings" in path_str and "kernel" not in path_str:
            return P(tensor, None)
        if any(n in path_str for n in ("query", "key", "value", "intermediate")) and path_str.endswith("kernel"):
            return P(fsdp, tensor)
        if ("attention/output" in path_str or "mlp/output" in path_str) and path_str.endswith("kernel"):
            return P(tensor, fsdp)
        if path_str.endswith("kernel"):
            return P(fsdp, None)
        return P()

    from unionml_tpu.models._sharding import shard_by_rules

    return shard_by_rules(params, spec_for)


# ---------------------------------------------------------------------- HF import

def import_hf_weights(hf_state_dict: Dict[str, Any], config: BertConfig) -> Dict[str, Any]:
    """Map a HuggingFace BERT state dict (torch tensors or numpy) onto this module.

    Accepts ``BertModel`` or ``BertForSequenceClassification`` state dicts; torch
    ``Linear`` weights are (out, in) and transpose to flax (in, out) kernels.
    """

    def t(name: str) -> np.ndarray:
        value = hf_state_dict[name]
        if hasattr(value, "detach"):
            value = value.detach().cpu().numpy()
        return np.asarray(value)

    def linear(prefix: str) -> Dict[str, np.ndarray]:
        return {"kernel": t(f"{prefix}.weight").T, "bias": t(f"{prefix}.bias")}

    def norm(prefix: str) -> Dict[str, np.ndarray]:
        return {"scale": t(f"{prefix}.weight"), "bias": t(f"{prefix}.bias")}

    prefix = "bert." if any(key.startswith("bert.") for key in hf_state_dict) else ""
    bert: Dict[str, Any] = {
        "word_embeddings": {"embedding": t(f"{prefix}embeddings.word_embeddings.weight")},
        "position_embeddings": {"embedding": t(f"{prefix}embeddings.position_embeddings.weight")},
        "token_type_embeddings": {"embedding": t(f"{prefix}embeddings.token_type_embeddings.weight")},
        "embeddings_norm": norm(f"{prefix}embeddings.LayerNorm"),
        "pooler": linear(f"{prefix}pooler.dense"),
        "encoder": {},
    }
    for i in range(config.num_layers):
        hf_layer = f"{prefix}encoder.layer.{i}"
        bert["encoder"][f"layer_{i}"] = {
            "attention": {
                "query": linear(f"{hf_layer}.attention.self.query"),
                "key": linear(f"{hf_layer}.attention.self.key"),
                "value": linear(f"{hf_layer}.attention.self.value"),
                "output": linear(f"{hf_layer}.attention.output.dense"),
                "output_norm": norm(f"{hf_layer}.attention.output.LayerNorm"),
            },
            "mlp": {
                "intermediate": linear(f"{hf_layer}.intermediate.dense"),
                "output": linear(f"{hf_layer}.output.dense"),
                "output_norm": norm(f"{hf_layer}.output.LayerNorm"),
            },
        }

    params: Dict[str, Any] = {"bert": bert}
    if "classifier.weight" in hf_state_dict:
        params["classifier"] = linear("classifier")
    else:
        rng = np.random.default_rng(0)
        params["classifier"] = {
            "kernel": rng.normal(0, 0.02, (config.hidden_size, config.num_labels)).astype(np.float32),
            "bias": np.zeros((config.num_labels,), dtype=np.float32),
        }
    return {"params": params}


def init_params(config: BertConfig, rng: Optional[jax.Array] = None, seq_len: int = 128) -> Any:
    """Random-init parameters for a BertForSequenceClassification."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    model = BertForSequenceClassification(config)
    dummy = jnp.zeros((1, seq_len), dtype=jnp.int32)
    return model.init({"params": rng}, dummy, deterministic=True)
