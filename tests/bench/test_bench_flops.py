"""Required-FLOP counts and the peaks table of the benchmark."""
import json

import numpy as np
import pytest

from bench_fixtures import REPO, TINY


def _cfg(name, **over):
    cfg = json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())
    cfg.update(over)
    return cfg


def test_smoke_widths_by_hand():
    from bench import flops

    cfg = _cfg("qwen2-0.5b", **TINY)
    # per layer: q and o 64*4*16 each, k and v 64*2*16 each, MLP 3*64*128;
    # two layers, then the tied LM head 64*512
    per_layer = 2 * 64 * 4 * 16 + 2 * 64 * 2 * 16 + 3 * 64 * 128
    assert flops.matmul_params(cfg) == 2 * per_layer + 64 * 512 == 106_496
    # causal attention once: 6 * layers * seq * heads * head_dim at seq 32
    assert flops.train_flops_per_token(cfg, 32) == 6 * 106_496 + 6 * 2 * 32 * 4 * 16


@pytest.mark.parametrize("name,seq,params,per_token", [
    ("qwen2-0.5b", 2048, 493_961_216, 3_228_008_448),
    ("qwen3-1.7b-l12", 4096, 915_144_704, 6_094_848_000),
])
def test_published_widths(name, seq, params, per_token):
    from bench import flops

    cfg = _cfg(name)
    assert flops.matmul_params(cfg) == params
    assert flops.train_flops_per_token(cfg, seq) == per_token


@pytest.mark.parametrize("name", ["qwen2-0.5b", "qwen3-1.7b-l12"])
def test_matmul_params_match_the_models_projections(name):
    """The count equals the program's projection weights plus the tied
    head, read from its parameter shapes (at smoke widths)."""
    from bench import flops
    from bench.cell import arch_of
    from repro.models.registry import build_model
    from repro.utils.trees import tree_paths

    cfg = _cfg(name, **TINY)
    shapes = tree_paths(build_model(arch_of(cfg)).param_shapes())
    proj = sum(int(np.prod(s.shape)) for p, s in shapes.items()
               if p.rsplit("/", 1)[-1] in ("wq", "wk", "wv", "wo", "wi", "wg"))
    assert flops.matmul_params(cfg) == proj + int(np.prod(shapes["embed"].shape))


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
