"""Required-FLOP counts and the peaks table of the benchmark.  A
configuration's FLOP per token comes from the reference module its file
names; the tests of the dense reference's count run on every configuration
that names it."""
import json

import numpy as np
import pytest

from bench_fixtures import REPO, TINY, configs

DENSE = configs(reference="bench/reference/dense.py")


def _cfg(name, **over):
    cfg = json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())
    cfg.update(over)
    return cfg


def _modules(cfg):
    """The reference and program modules that ``cfg`` names."""
    from bench import spec

    return spec.config_modules(cfg, REPO / "bench" / "configs" / "test.json", REPO)


def test_smoke_widths_by_hand():
    cfg = _cfg("qwen2-0.5b", **TINY)
    ref = _modules(cfg)["reference"]
    # per layer: q and o 64*4*16 each, k and v 64*2*16 each, MLP 3*64*128;
    # two layers, then the tied LM head 64*512
    per_layer = 2 * 64 * 4 * 16 + 2 * 64 * 2 * 16 + 3 * 64 * 128
    assert ref.matmul_params(cfg) == 2 * per_layer + 64 * 512 == 106_496
    # causal attention once: 6 * layers * seq * heads * head_dim at seq 32
    assert ref.train_flops_per_token(cfg, 32) == 6 * 106_496 + 6 * 2 * 32 * 4 * 16


@pytest.mark.parametrize("entry", DENSE, ids=lambda c: c["name"])
def test_published_widths(entry):
    """The count the file states is 6 x the matmul parameters plus causal
    attention once, 6 x layers x seq x heads x head_dim."""
    cfg = json.loads((REPO / entry["file"]).read_text())
    ref = _modules(cfg)["reference"]
    seq, per_token = cfg["flop_per_token"]["seq_len"], cfg["flop_per_token"]["value"]
    attention = 6 * cfg["num_hidden_layers"] * seq * cfg["num_attention_heads"] \
        * cfg["head_dim"]
    assert 6 * ref.matmul_params(cfg) + attention == per_token


@pytest.mark.parametrize("entry", DENSE, ids=lambda c: c["name"])
def test_matmul_params_match_the_models_projections(entry):
    """The count equals the program's projection weights plus the tied
    head, read from its parameter shapes (at smoke widths)."""
    from repro.models.registry import build_model
    from repro.utils.trees import tree_paths

    cfg = json.loads((REPO / entry["file"]).read_text())
    cfg.update(TINY)
    mods = _modules(cfg)
    shapes = tree_paths(build_model(mods["program"].arch_of(cfg)).param_shapes())
    proj = sum(int(np.prod(s.shape)) for p, s in shapes.items()
               if p.rsplit("/", 1)[-1] in ("wq", "wk", "wv", "wo", "wi", "wg"))
    assert mods["reference"].matmul_params(cfg) == \
        proj + int(np.prod(shapes["embed"].shape))


def test_peaks_by_device_kind():
    from bench import flops

    v5e = flops.peak("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peak("TPU v9 imaginary")


def test_flash_attention_roofline_reads_kernel_events():
    from bench import flops, spec, trace
    from bench.cell import Reading

    cfg, mix = _cfg("qwen2-0.5b"), {"global_batch": 4, "seq_len": 2048}
    peak = flops.peak("TPU v5 lite")
    flop, byts = flops.flash_attention_fwd(4, 14, 2, 2048, 64)
    least_ns = max(flop / peak["bf16_flops_per_s"], byts / peak["hbm_bytes_per_s"]) * 1e9
    # two calls, each taking twice the least time: half the roofline
    ev = [("flash_attention_fwd.1", 0, int(2 * least_ns)),
          ("flash_attention_fwd.2", int(3 * least_ns), int(5 * least_ns))]
    r = Reading(trace.Reduced((0, int(6 * least_ns)), {"d": ev}), 1, [], cfg,
                mix, 1, peak)
    assert spec.metric_reader("flash_attention_roofline")(r) == pytest.approx(50.0, rel=1e-4)
