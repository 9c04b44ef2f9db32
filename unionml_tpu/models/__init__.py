"""Model zoo: jax-native models the framework owns end-to-end.

The reference owns no models (users bring sklearn/torch/keras callables); here the
digits/MNIST/BERT baseline configs ship as compiled flax modules with train steps,
shardings, and checkpointing.
"""

from unionml_tpu.models.bert import (
    BertConfig,
    BertForSequenceClassification,
    BertModel,
    import_hf_weights,
    init_params,
    param_shardings,
)
# GPT helpers export under gpt-prefixed names: bare `generate`/`lm_loss` would
# collide with future decoder families the way init_params already collided with
# BERT's. Module-qualified access (models.gpt.generate) remains canonical.
from unionml_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from unionml_tpu.models.gpt import generate as gpt_generate
from unionml_tpu.models.gpt import init_cache as init_gpt_cache
from unionml_tpu.models.gpt import import_hf_weights as import_hf_gpt_weights
from unionml_tpu.models.gpt import init_params as init_gpt_params
from unionml_tpu.models.gpt import lm_loss as gpt_lm_loss
from unionml_tpu.models.gpt import KVCacheLayout
from unionml_tpu.models.latent_moe import LatentCacheLayout, LatentMoEConfig, LatentMoELMHeadModel
from unionml_tpu.models.mlp import CNNClassifier, MLPClassifier
from unionml_tpu.models.phi4flash import HybridCacheLayout, Phi4FlashConfig, Phi4FlashLMHeadModel
from unionml_tpu.models.moe import (
    MoEMlp,
    collect_aux_losses,
    load_balancing_loss,
    router_z_loss,
)
from unionml_tpu.models.training import (
    FitResult,
    TrainState,
    create_train_state,
    dict_batches,
    fit,
    fit_lm,
    make_classifier_eval_step,
    make_classifier_train_step,
    make_lm_eval_step,
    make_lm_train_step,
)

__all__ = [
    "BertConfig",
    "BertForSequenceClassification",
    "BertModel",
    "CNNClassifier",
    "FitResult",
    "MoEMlp",
    "import_hf_gpt_weights",
    "collect_aux_losses",
    "load_balancing_loss",
    "router_z_loss",
    "GPTConfig",
    "GPTLMHeadModel",
    "HybridCacheLayout",
    "KVCacheLayout",
    "LatentCacheLayout",
    "LatentMoEConfig",
    "LatentMoELMHeadModel",
    "MLPClassifier",
    "Phi4FlashConfig",
    "Phi4FlashLMHeadModel",
    "fit_lm",
    "gpt_generate",
    "gpt_lm_loss",
    "make_lm_eval_step",
    "make_lm_train_step",
    "init_gpt_cache",
    "init_gpt_params",
    "TrainState",
    "create_train_state",
    "dict_batches",
    "fit",
    "import_hf_weights",
    "init_params",
    "make_classifier_eval_step",
    "make_classifier_train_step",
    "param_shardings",
]
