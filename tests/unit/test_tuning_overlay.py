"""TUNING_MEASURED.json overlay: distillation from sweep artifacts + table merge."""

import importlib
import json

import pytest


def test_distill_promotes_only_timing_valid_and_safe(tmp_path):
    from tools.promote_tuning import distill

    (tmp_path / "KERNEL_BENCH.json").write_text(json.dumps({
        "timing_valid": True,
        "results": {
            "b8_h12_s128_d64": {"verdict": "use_xla", "best": {"block_q": 128, "block_k": 128, "fwdbwd_ms": 1.0, "max_err_vs_xla": 0.01}},
            "b2_h12_s1024_d64": {"verdict": "use_pallas", "best": {"block_q": 512, "block_k": 512, "fwdbwd_ms": 0.9, "max_err_vs_xla": 0.05}},
            "b2_h2_s256_d64": {"verdict": "use_pallas", "best": {"block_q": 256, "block_k": 256, "fwdbwd_ms": 0.5, "max_err_vs_xla": 0.9}},
            "b2_h16_s512_d128": {"verdict": "use_pallas", "xla_fwdbwd_ms": 1.0, "best": {"block_q": 512, "block_k": 512, "fwdbwd_ms": 0.99, "max_err_vs_xla": 0.01}},
        },
    }))
    # CPU correctness sweep must contribute nothing
    (tmp_path / "PACKED_KERNEL_BENCH.json").write_text(json.dumps({
        "timing_valid": False,
        "results": {"b8_h12_s128_d64": {"verdict": "use_pallas"}},
    }))
    overlay = distill(tmp_path)
    assert overlay["measured_impl"]["128,128,64"] == "xla"
    assert overlay["measured_impl"]["1024,1024,64"] == "pallas"
    # numerically-unsafe winner demoted to xla, and no block promotion for it
    assert overlay["measured_impl"]["256,256,64"] == "xla"
    # a <2% win is a tie: break toward the arbiter-validated default
    assert overlay["measured_impl"]["512,512,128"] == "xla"
    # measured best blocks promote for every numerically-safe shape (they serve
    # the impl="pallas" escape hatch even where xla won), never for unsafe ones
    assert overlay["tuned_blocks"] == {
        "128,128,64": [128, 128],
        "1024,1024,64": [512, 512],
        "512,512,128": [512, 512],
    }
    assert overlay["measured_packed_impl"] == {}
    assert overlay["packed_tuned_blocks"] == {}


def test_distill_paged_verdicts_and_heads(tmp_path):
    """Paged sweep → rank-4 verdicts: ties break toward PALLAS (the byte-model
    default) and the int8 entry wins the shared dispatch key. The kernel sizes
    its own grid, so no tiling rides along — an older artifact's
    ``heads_per_step`` is read past."""
    from tools.promote_tuning import distill_paged

    (tmp_path / "PAGED_KERNEL_BENCH.json").write_text(json.dumps({
        "timing_valid": True,
        "results": {
            # dense says xla, int8 says pallas: int8 wins the shared key
            "w16_bs16_h12_d64_bf16": {"verdict": "use_xla", "xla_fwd_ms": 0.5,
                                      "best": {"heads_per_step": 1, "fwd_ms": 0.6}},
            "w16_bs16_h12_d64_int8": {"verdict": "use_pallas", "xla_fwd_ms": 0.9,
                                      "best": {"heads_per_step": 4, "fwd_ms": 0.4}},
            # xla "won" by <2%: a tie, broken toward the paged default (pallas)
            "w32_bs16_h12_d64_int8": {"verdict": "use_xla", "xla_fwd_ms": 0.99,
                                      "best": {"heads_per_step": 2, "fwd_ms": 1.0}},
            # kernel failed to lower at this shape: honest demotion
            "w8_bs16_h16_d128_int8": {"verdict": "pallas_failed_use_xla"},
        },
    }))
    overlay = distill_paged(tmp_path)
    assert overlay["measured_paged_impl"] == {
        "16,16,12,64": "pallas",
        "32,16,12,64": "pallas",
        "8,16,16,128": "xla",
    }
    assert set(overlay) == {"measured_paged_impl"}

    # a CPU correctness artifact contributes nothing
    (tmp_path / "PAGED_KERNEL_BENCH.json").write_text(json.dumps({
        "timing_valid": False,
        "results": {"w16_bs16_h12_d64_int8": {"verdict": "use_pallas"}},
    }))
    assert distill_paged(tmp_path) == {"measured_paged_impl": {}}


def test_promote_merges_with_existing_overlay(tmp_path):
    """A window with one failed sweep must not erase the other table's verdicts."""
    import sys

    sys.modules.pop("tools.promote_tuning", None)
    from tools import promote_tuning

    (tmp_path / "TUNING_MEASURED.json").write_text(json.dumps({
        "measured_packed_impl": {"512,512,64": "pallas"},
        "packed_tuned_blocks": {"512,512,64": [256, 256]},
    }))
    (tmp_path / "KERNEL_BENCH.json").write_text(json.dumps({
        "timing_valid": True,
        "results": {"b8_h12_s128_d64": {"verdict": "use_xla", "best": {
            "block_q": 128, "block_k": 128, "fwdbwd_ms": 1.0, "max_err_vs_xla": 0.01}}},
    }))
    # no PACKED artifact at all this "window"
    overlay = promote_tuning.distill(tmp_path)
    import unittest.mock as mock

    with mock.patch.object(promote_tuning, "REPO", tmp_path), \
         mock.patch.object(promote_tuning, "distill", lambda *_: overlay):
        promote_tuning.main()
    merged = json.loads((tmp_path / "TUNING_MEASURED.json").read_text())
    assert merged["measured_packed_impl"] == {"512,512,64": "pallas"}  # preserved
    assert merged["packed_tuned_blocks"] == {"512,512,64": [256, 256]}
    assert merged["measured_impl"] == {"128,128,64": "xla"}


def test_overlay_merges_into_tables(tmp_path, monkeypatch):
    import unionml_tpu.ops.tuning as tuning

    overlay = {
        "measured_packed_impl": {"128,128,64": "pallas"},
        "measured_impl": {"4096,4096,64": "pallas"},
        "tuned_blocks": {"4096,4096,64": [512, 512]},
        # rank-4 paged verdicts, with malformed entries that must be dropped
        "measured_paged_impl": {"16,16,12,64": "xla", "16,16,12": "pallas",
                                "32,16,12,64": "cuda"},
        # a key from before the kernel sized its own grid: read past, no error
        "paged_tuned_heads": {"16,16,12,64": 4, "32,16,12,64": True},
    }
    path = tmp_path / "TUNING_MEASURED.json"
    path.write_text(json.dumps(overlay))

    real_open = open

    def fake_open(name, *args, **kwargs):
        if str(name).endswith("TUNING_MEASURED.json"):
            return real_open(path, *args, **kwargs)
        return real_open(name, *args, **kwargs)

    monkeypatch.setattr("builtins.open", fake_open)
    try:
        importlib.reload(tuning)
        assert tuning.pick_packed_impl(128, 128, 64) == "pallas"
        assert tuning.pick_packed_impl(512, 512, 64) == tuning.DEFAULT_PACKED_IMPL
        assert tuning.pick_impl(4096, 4096, 64) == "pallas"
        assert tuning.pick_block_sizes(4096, 4096, 64) == (512, 512)
        # paged: the measured demotion lands; malformed keys/values are dropped
        assert tuning.pick_paged_impl(16, 16, 12, 64) == "xla"
        assert tuning.pick_paged_impl(32, 16, 12, 64) == tuning.DEFAULT_PAGED_IMPL
        # the overlay above still carries "paged_tuned_heads": it loaded, and
        # nothing of the table is left to receive it
        assert not hasattr(tuning, "PAGED_TUNED_HEADS")
        assert not hasattr(tuning, "pick_paged_heads")
    finally:
        monkeypatch.undo()
        importlib.reload(tuning)  # restore the real tables for later tests
