"""End-to-end behaviour tests: multi-device collectives battery, attention
implementations, MoE dispatch, HLO parsing, roofline analytics, data
pipeline determinism, serving."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import REPO, run_multi_device

HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# multi-device battery (subprocess with 8 fake devices)
# ---------------------------------------------------------------------------


def test_multi_device_collectives_battery():
    out = run_multi_device(os.path.join(HERE, "batteries", "collectives_battery.py"))
    assert "ALL OK" in out


def test_multi_device_slow_split_battery():
    out = run_multi_device(os.path.join(HERE, "batteries", "slow_split_battery.py"))
    assert "ALL OK" in out


def test_multi_device_train_battery():
    out = run_multi_device(os.path.join(HERE, "batteries", "train_battery.py"),
                           timeout=900)
    assert "ALL OK" in out


# ---------------------------------------------------------------------------
# attention implementations agree
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,block", [(256, 64), (512, 128)])
def test_attention_masked_vs_tri(S, block):
    from repro.models.layers import attend
    ks = jax.random.split(jax.random.key(0), 3)
    B, H, KV, hd = 2, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    o1 = attend(q, k, v, causal=True, impl="masked", q_chunk=64, kv_chunk=64)
    o2 = attend(q, k, v, causal=True, impl="tri", block=block,
                q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-4)


def test_attention_vs_kernel_ref():
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.models.layers import attend
    ks = jax.random.split(jax.random.key(1), 3)
    B, S, H, KV, hd = 1, 128, 4, 2, 16
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    o1 = attend(q, k, v, causal=True, impl="masked", q_chunk=32, kv_chunk=32)
    o2 = attention_ref(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                       jnp.moveaxis(v, 1, 2), causal=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(jnp.moveaxis(o2, 2, 1)),
                               rtol=2e-4, atol=2e-4)


# (Sq, Sk, H, KV, q_chunk, kv_chunk, causal, gqa_repeat, dtype); the chunk
# counts per side follow from the lengths and chunks
_FLASH_CASES = {
    "causal-1x1": (64, 64, 4, 2, 64, 64, True, False, "float32"),
    "causal-2x2": (128, 128, 4, 2, 64, 64, True, False, "float32"),
    "causal-4x4": (128, 128, 4, 2, 32, 32, True, False, "float32"),
    "causal-2x4": (128, 128, 4, 2, 64, 32, True, False, "float32"),
    "causal-4x2": (128, 128, 4, 2, 32, 64, True, False, "float32"),
    "full-4x4": (128, 128, 4, 2, 32, 32, False, False, "float32"),
    "cross-2x4": (64, 128, 4, 2, 32, 32, False, False, "float32"),
    "cross-4x1": (128, 32, 4, 2, 32, 32, False, False, "float32"),
    "gqa-g4": (128, 128, 8, 2, 32, 32, True, False, "float32"),
    "gqa-repeat": (128, 128, 8, 2, 64, 64, True, True, "float32"),
    "mha-g1": (128, 128, 4, 4, 32, 32, True, False, "float32"),
    "fit-shrinks": (96, 96, 4, 2, 64, 64, True, False, "float32"),
    "bf16-causal": (128, 128, 4, 2, 32, 32, True, False, "bfloat16"),
    "bf16-cross": (64, 128, 4, 2, 32, 64, False, False, "bfloat16"),
}


@pytest.mark.parametrize("case", list(_FLASH_CASES))
def test_masked_attention_and_grads_match_refs(case):
    """``attend(impl="masked")`` (the hand-written flash backward) gives the
    output and dq, dk, dv of ``jax.vjp`` of the dense f32 reference and of
    ``impl="tri"``.  f32 agrees to f32 rounding; bf16 inputs are held to
    2^-6 of the largest magnitude, four times one bf16 rounding (2^-8) of
    each result."""
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.models.layers import attend
    Sq, Sk, H, KV, qc, kc, causal, rep, dt = _FLASH_CASES[case]
    B, hd = 2, 16
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (B, Sq, H, hd)).astype(dt)
    k = jax.random.normal(ks[1], (B, Sk, KV, hd)).astype(dt)
    v = jax.random.normal(ks[2], (B, Sk, KV, hd)).astype(dt)
    g = jax.random.normal(ks[3], (B, Sq, H, hd)).astype(dt)

    def masked(q, k, v):
        return attend(q, k, v, causal=causal, impl="masked", q_chunk=qc,
                      kv_chunk=kc, gqa_repeat=rep)

    def tri(q, k, v):
        return attend(q, k, v, causal=causal, impl="tri", block=Sq // 2,
                      q_chunk=qc, kv_chunk=kc, gqa_repeat=rep)

    def ref(q, k, v):  # (B, S, heads, hd) in and out, f32 throughout
        f = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 2)
        return jnp.moveaxis(attention_ref(f(q), f(k), f(v), causal=causal), 2, 1)

    def out_and_grads(fn):
        @jax.jit
        def run(q, k, v, g):
            o, vjp = jax.vjp(fn, q, k, v)
            return (o, *vjp(g.astype(o.dtype)))
        return [np.asarray(x.astype(jnp.float32)) for x in run(q, k, v, g)]

    got = out_and_grads(masked)
    tol = 2e-4 if dt == "float32" else 2.0 ** -6
    for want in (out_and_grads(ref), out_and_grads(tri)):
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            assert a.dtype == b.dtype and a.shape == b.shape, name
            np.testing.assert_allclose(a, b, rtol=tol,
                                       atol=tol * float(np.abs(b).max()),
                                       err_msg=f"{case}: {name}")


@pytest.mark.parametrize("S,causal,want", [
    (2048, True, (3, 4)), (4096, True, (10, 16)),
    (2048, False, (4, 4)), (4096, False, (16, 16)),
])
def test_masked_attention_tile_schedule(S, causal, want):
    """With 1024-wide chunks causal self-attention visits only the tiles on
    or below the diagonal; without the mask it visits every tile."""
    from repro.models.layers import attn_tile_counts
    assert attn_tile_counts(S, S, 1024, 1024, causal) == want


# ---------------------------------------------------------------------------
# MoE dispatch correctness vs brute force
# ---------------------------------------------------------------------------


def test_moe_matches_bruteforce_at_full_capacity():
    from repro.configs.base import ArchConfig, MoEConfig
    from repro.models.layers import _act, apply_moe, init_moe
    arch = ArchConfig(name="t", family="moe", n_layers=1, d_model=16,
                      n_heads=2, n_kv_heads=2, d_ff=32, vocab=64,
                      moe=MoEConfig(num_experts=4, top_k=2, expert_d_ff=32,
                                    capacity_factor=4.0))
    p = init_moe(arch, jax.random.key(0), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 8, 16))
    out, aux = apply_moe(arch, p, x)
    assert np.isfinite(np.asarray(out)).all() and float(aux) > 0

    # brute force: compute every expert densely, combine with the same gates
    T = 16
    xt = x.reshape(T, 16)
    logits = xt @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    gv, gi = jax.lax.top_k(probs, 2)
    gv = gv / gv.sum(-1, keepdims=True)
    dense = []
    for e in range(4):
        h = _act(arch.activation, xt @ p["we_in"][e])
        h = h * (xt @ p["we_gate"][e])
        dense.append(h @ p["we_out"][e])
    dense = jnp.stack(dense, 1)  # (T, E, d)
    expect = jnp.einsum("tk,tkd->td",
                        gv, jnp.take_along_axis(dense, gi[..., None], axis=1))
    np.testing.assert_allclose(np.asarray(out.reshape(T, 16)),
                               np.asarray(expect), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# HLO parser units
# ---------------------------------------------------------------------------


def test_hlo_parser_iota_groups():
    from repro.roofline.hlo_parse import _parse_replica_groups
    g = _parse_replica_groups("[4,2]<=[2,4]T(1,0)")
    # arange(8).reshape(2,4).T -> [[0,4],[1,5],[2,6],[3,7]]
    assert g == [[0, 4], [1, 5], [2, 6], [3, 7]]
    g2 = _parse_replica_groups("{{0,1,2,3},{4,5,6,7}}")
    assert g2 == [[0, 1, 2, 3], [4, 5, 6, 7]]


def test_hlo_parser_tier_classification():
    from repro.roofline.hlo_parse import classify_groups
    assert classify_groups([[0, 1, 2, 3]], chips_per_pod=4) == "ici"
    assert classify_groups([[0, 4], [1, 5]], chips_per_pod=4) == "dcn"
    assert classify_groups([[0, 1, 4, 5]], chips_per_pod=4) == "dcn"


def test_hlo_parser_trip_counts():
    from repro.roofline.hlo_parse import parse_collectives
    hlo = """
%body (p: (s32[], f32[128])) -> (s32[], f32[128]) {
  %ar = f32[128]{0} all-reduce(%x), replica_groups={{0,1},{2,3}}, to_apply=%sum
}

%cond (p: (s32[], f32[128])) -> pred[] {
  %c = s32[] constant(12)
}

ENTRY %main (p: f32[128]) -> f32[128] {
  %w = (s32[], f32[128]) while(%t), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"12"}}
}
"""
    s = parse_collectives(hlo, chips_per_pod=2)
    assert len(s.ops) == 1
    op = s.ops[0]
    assert op.multiplier == 12 and op.tier == "ici"
    assert op.wire_bytes == 12 * 512 * 1.0  # 2*(2-1)/2 * 512B * 12


# ---------------------------------------------------------------------------
# roofline analytics vs XLA (unrolled => cost_analysis exact)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["qwen3-1.7b", "deepseek-moe-16b", "rwkv6-1.6b"])
def test_analytics_matches_xla_costs(name):
    from repro.configs import ShapeConfig, get_smoke_arch
    from repro.models import ModelSettings, build_model
    from repro.roofline.analytics import model_cost
    st = ModelSettings(param_dtype="float32", compute_dtype="float32",
                       remat="none", scan_layers=False, attn_impl="masked",
                       loss_chunk=64, max_seq=128, attn_chunk=4096)
    m = build_model(get_smoke_arch(name), st)
    shape = ShapeConfig("t", 64, 4, "train")
    params = m.init(jax.random.key(0))
    c = jax.jit(lambda p, t: m.prefill(p, t)[0]).lower(
        params, jnp.zeros((4, 64), jnp.int32)).compile()
    hlo_flops = c.cost_analysis()["flops"]
    est = model_cost(m, shape, "prefill")["fwd_flops"]
    assert 0.85 < est / hlo_flops < 1.15, (est, hlo_flops)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_data_pipeline_determinism_and_sharding():
    from repro.configs import get_smoke_arch
    from repro.data.pipeline import DataConfig, TokenPipeline

    class Sh:
        global_batch, seq_len = 8, 32

    arch = get_smoke_arch("qwen2-0.5b")
    p1 = TokenPipeline(arch, Sh(), DataConfig(seed=5), host_index=0, host_count=2)
    p2 = TokenPipeline(arch, Sh(), DataConfig(seed=5), host_index=0, host_count=2)
    b1, b2 = p1.batch_at(17), p2.batch_at(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])  # deterministic
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    # different hosts see different data
    p3 = TokenPipeline(arch, Sh(), DataConfig(seed=5), host_index=1, host_count=2)
    assert not np.array_equal(p3.batch_at(17)["tokens"], b1["tokens"])
    assert b1["tokens"].shape == (4, 32)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def test_decode_server_continuous_batching():
    from repro.configs import get_smoke_arch
    from repro.models import ModelSettings, build_model
    from repro.runtime.serve_loop import DecodeServer, Request
    st = ModelSettings(param_dtype="float32", compute_dtype="float32",
                       remat="none", max_seq=64)
    model = build_model(get_smoke_arch("qwen2-0.5b"), st)
    from repro.utils.jax_compat import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    params = model.init(jax.random.key(0))
    server = DecodeServer(model, mesh, batch_slots=2, max_seq=64)
    for i in range(5):  # more requests than slots -> queueing + swap
        server.submit(Request(uid=i, prompt=np.array([1, 2, 3], np.int32),
                              max_new=4))
    outs = server.run(params, max_steps=40)
    assert len(outs) == 5
    assert all(len(toks) == 4 for toks in outs.values())
    assert server.throughput() > 0


def test_dryrun_exits_nonzero_when_a_cell_fails(tmp_path):
    """A failed cell is recorded with ``ok: False`` AND fails the run."""
    import subprocess
    import sys
    code = ("import sys\n"
            "import repro.launch.dryrun as d\n"
            "d.run_cell = lambda *a, **k: {'ok': False, 'error': 'boom'}\n"
            "sys.argv = ['dryrun', '--arch', 'qwen2-0.5b', '--shape',\n"
            "            'train_4k', '--mesh', 'single', '--out', sys.argv[1]]\n"
            "d.main()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert "1 cell(s) failed" in proc.stderr
    assert list(tmp_path.glob("*.json"))
