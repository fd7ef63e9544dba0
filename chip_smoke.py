"""Smoke test of the training path on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the cross-chip path on a 2x2 host

With no option it runs, in one process, on ``jax.devices()[:1]``:

  * train   — ``qwen2-0.5b`` at its published widths through
              ``build_model`` -> ``Trainer.train()`` in ``mode="dfabric"``
              (bf16, full remat, global batch 4 x seq 2048, a few steps);
              every loss must be finite and the last below the first;
  * kernels — each Pallas kernel once, compiled, at a real model width,
              against its ``ref.py`` at the tolerance of
              ``tests/test_kernels.py``;
  * decode  — ``DecodeServer`` at the same width: every request must get
              all of its tokens.

``--chips 4`` runs only the path that exists across chips: the same model
in float32 on a (pod, data, model) = (2, 2, 1) mesh, once with DFabric's
tier-wise gradient sync and once with XLA's own collectives, from the same
seed and data; the two loss curves must agree (see ``DP_LOSS_RTOL``).

A failure in any phase exits non-zero.  So does a backend without a TPU:
nothing here falls back to the CPU.  Only after every phase has passed
does the last line of stdout print
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path
from typing import Dict, List, Sequence

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import ArchConfig, ShapeConfig, get_arch  # noqa: E402
from repro.kernels.flash_attention.kernel import flash_attention_fwd  # noqa: E402
from repro.kernels.flash_attention.ref import attention_ref  # noqa: E402
from repro.kernels.mamba_scan.kernel import mamba_scan_fwd  # noqa: E402
from repro.kernels.mamba_scan.ref import mamba_scan_ref  # noqa: E402
from repro.kernels.wkv6.kernel import wkv6_fwd  # noqa: E402
from repro.kernels.wkv6.ref import wkv6_ref  # noqa: E402
from repro.launch.cells import cell_settings  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.models.transformer import ModelSettings  # noqa: E402
from repro.runtime.serve_loop import DecodeServer, Request  # noqa: E402
from repro.runtime.train_loop import Trainer, TrainerConfig  # noqa: E402
from repro.utils.jax_compat import make_mesh  # noqa: E402

ARCH = "qwen2-0.5b"
MESH_AXES = ("pod", "data", "model")
TRAIN = dict(batch=4, seq=2048, steps=6)
DP = dict(batch=4, seq=2048, steps=4)
DECODE = dict(requests=6, max_new=8, slots=4, max_seq=64)
LR, WARMUP = 3e-3, 1

#: kernel inputs at real widths: flash attention at qwen2-0.5b (14 q / 2 kv
#: heads, head dim 64, S 2048), WKV6 at rwkv6-1.6b (32 heads of 64, S 4096),
#: the selective scan at jamba-1.5-large (d_inner 16384, d_state 16, S 2048)
KERNEL_WIDTHS = {
    "flash_attention": dict(B=1, H=14, KV=2, S=2048, hd=64),
    "wkv6": dict(B=1, H=32, S=4096, hd=64),
    "mamba_scan": dict(B=1, S=2048, di=16384, ds=16),
}

#: --chips 4: dfabric and gspmd run the same float32 math (matmuls at
#: HIGHEST precision); they differ only in reduction order — tier-wise
#: reduce-scatter / psum / all-gather against XLA's all-reduce, and how XLA
#: partitions the step.  That is float32 rounding (~1e-7 relative) carried
#: through a few AdamW steps; a sync that drops or double-counts a replica's
#: gradient moves the loss by orders of magnitude more.
DP_LOSS_RTOL = 1e-4


def device_summary() -> Dict[str, object]:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(chips: int) -> None:
    """Exit non-zero unless JAX sees at least ``chips`` TPU devices."""
    dev = device_summary()
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX backend is "
                         f"{dev['platform']!r}); nothing was run")
    if dev["count"] < chips:
        raise SystemExit(f"chip_smoke: {chips} chips requested, JAX sees "
                         f"{dev['count']}")


def mesh_of(shape: Sequence[int]):
    n = int(np.prod(shape))
    return make_mesh(tuple(shape), MESH_AXES, devices=jax.devices()[:n])


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def train(arch: ArchConfig, mesh, *, mode: str, batch: int, seq: int,
          steps: int, dtype: str = "bfloat16", seed: int = 0) -> List[Dict]:
    """``steps`` steps of ``Trainer.train()`` at ``cells.cell_settings``
    (full remat) in ``dtype``; the per-step metrics."""
    shape = ShapeConfig("chip_smoke", seq, batch, "train")
    settings = dataclasses.replace(cell_settings(arch, shape),
                                   param_dtype=dtype, compute_dtype=dtype)
    cfg = TrainerConfig(steps=steps, lr=LR, warmup=WARMUP, log_every=1,
                        mode=mode, seed=seed)
    return Trainer(build_model(arch, settings), mesh, shape,
                   cfg).train()["metrics"]


def check_losses(losses: Sequence[float], what: str) -> None:
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{what}: non-finite loss in {list(losses)}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{what}: loss did not fall "
                             f"({losses[0]} -> {losses[-1]})")


def train_phase(arch: ArchConfig, *, batch: int, seq: int, steps: int
                ) -> Dict[str, float]:
    """The one-chip DFabric train path in bf16."""
    metrics = train(arch, mesh_of((1, 1, 1)), mode="dfabric", batch=batch,
                    seq=seq, steps=steps)
    losses = [m["loss"] for m in metrics]
    check_losses(losses, f"train {arch.name}")
    steady = statistics.median(m["dt"] for m in metrics[1:])
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"train {arch.name} d_model={arch.d_model} layers={arch.n_layers} "
          f"vocab={arch.vocab} batch={batch} seq={seq} bf16 remat=full")
    print(f"train losses {[round(l, 4) for l in losses]}")
    print(f"train first step wall {metrics[0]['dt']:.3f} s (compile "
          f"included); steady step wall median {steady:.4f} s over "
          f"{len(metrics) - 1} steps")
    print(f"train peak_bytes_in_use {peak if peak is not None else 'not reported'}")
    return {"first_loss": losses[0], "last_loss": losses[-1],
            "first_step_s": metrics[0]["dt"], "steady_step_s": steady}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _check(name: str, got, want, *, rtol: float, atol: float) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                               err_msg=f"kernel {name}")
    return err


def kernel_phase(widths: Dict[str, Dict[str, int]], *,
                 interpret: bool = False) -> Dict[str, float]:
    """Each kernel once against its reference; returns the max abs error
    of each output.  References run with float32 matmuls (HIGHEST)."""
    errs: Dict[str, float] = {}
    with jax.default_matmul_precision("highest"):
        w = widths["flash_attention"]
        B, H, KV, S, hd = w["B"], w["H"], w["KV"], w["S"], w["hd"]
        ks = jax.random.split(jax.random.key(0), 3)
        # tolerances of tests/test_kernels.py::test_flash_attention
        for dtype, tol in ((jnp.bfloat16, 2e-2), (jnp.float32, 1e-5)):
            q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
            k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
            v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
            out = flash_attention_fwd(q, k, v, causal=True, interpret=interpret)
            exp = jax.jit(attention_ref, static_argnames="causal")(
                q, k, v, causal=True)
            errs[f"flash_attention[{jnp.dtype(dtype).name}]"] = _check(
                "flash_attention", out, exp, rtol=tol * 10, atol=tol * 10)

        w = widths["wkv6"]
        B, H, S, hd = w["B"], w["H"], w["S"], w["hd"]
        ks = jax.random.split(jax.random.key(1), 6)
        r, k, v = (jax.random.normal(ks[i], (B, H, S, hd)) for i in range(3))
        dec = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, H, S, hd)) * 0.5))
        u = jax.random.normal(ks[4], (H, hd)) * 0.1
        s0 = jax.random.normal(ks[5], (B, H, hd, hd)) * 0.1
        y1, st1 = wkv6_fwd(r, k, v, dec, u, s0, interpret=interpret)
        y2, st2 = jax.jit(wkv6_ref)(r, k, v, dec, u, s0)
        # tolerance of tests/test_kernels.py::test_wkv6
        atol = 2e-5 * (float(jnp.max(jnp.abs(y2))) + 1.0)
        errs["wkv6.y"] = _check("wkv6 y", y1, y2, rtol=1e-4, atol=atol)
        errs["wkv6.state"] = _check("wkv6 state", st1, st2, rtol=1e-4, atol=atol)

        w = widths["mamba_scan"]
        B, S, di, ds = w["B"], w["S"], w["di"], w["ds"]
        ks = jax.random.split(jax.random.key(2), 6)
        u = jax.random.normal(ks[0], (B, S, di))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)) - 2)
        A = -jnp.exp(jax.random.normal(ks[2], (di, ds)) * 0.3)
        Bc = jax.random.normal(ks[3], (B, S, ds))
        Cc = jax.random.normal(ks[4], (B, S, ds))
        D = jnp.ones((di,))
        h0 = jax.random.normal(ks[5], (B, di, ds)) * 0.1
        y1, h1 = mamba_scan_fwd(u, dt, A, Bc, Cc, D, h0, interpret=interpret)
        y2, h2 = jax.jit(mamba_scan_ref)(u, dt, A, Bc, Cc, D, h0)
        # tolerance of tests/test_kernels.py::test_mamba_scan
        errs["mamba_scan.y"] = _check("mamba_scan y", y1, y2, rtol=1e-4, atol=1e-4)
        errs["mamba_scan.state"] = _check("mamba_scan state", h1, h2,
                                          rtol=1e-4, atol=1e-4)
    for name, err in errs.items():
        print(f"kernel {name} compiled={not interpret} max_abs_err {err:.3e} "
              f"within tolerance")
    return errs


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def decode_phase(arch: ArchConfig, *, requests: int, max_new: int, slots: int,
                 max_seq: int, seed: int = 0) -> Dict[str, float]:
    """``DecodeServer`` on one chip: every request must get ``max_new``
    tokens."""
    settings = ModelSettings(param_dtype="bfloat16", compute_dtype="bfloat16",
                             remat="none", max_seq=max_seq)
    model = build_model(arch, settings)
    server = DecodeServer(model, make_mesh((1, 1), ("data", "model"),
                                           devices=jax.devices()[:1]),
                          batch_slots=slots, max_seq=max_seq, seed=seed)
    rng = np.random.default_rng(seed)
    for uid in range(requests):
        prompt = rng.integers(0, arch.vocab, size=(4,)).astype(np.int32)
        server.submit(Request(uid=uid, prompt=prompt, max_new=max_new))
    outputs = server.run(model.init(jax.random.key(seed)), max_steps=max_seq - 1)
    short = {uid: len(t) for uid, t in outputs.items() if len(t) != max_new}
    if len(outputs) != requests or short:
        raise AssertionError(f"decode: {len(outputs)}/{requests} requests, "
                             f"short ones {short}")
    print(f"decode {arch.name} requests={requests} tokens_each={max_new} "
          f"slots={slots} steps={server.stats['steps']} wall "
          f"{server.stats['wall']:.3f} s (compile included): every request "
          f"finished")
    return {"steps": server.stats["steps"], "tokens": server.stats["tokens"]}


# ---------------------------------------------------------------------------
# --chips 4: DFabric sync vs XLA collectives on a (2, 2, 1) mesh
# ---------------------------------------------------------------------------


def dp_phase(arch: ArchConfig, *, batch: int, seq: int, steps: int
             ) -> Dict[str, List[float]]:
    """Train in float32 on a (pod, data, model) = (2, 2, 1) mesh in both
    step modes; the loss curves must agree to ``DP_LOSS_RTOL``."""
    mesh = mesh_of((2, 2, 1))
    curves = {}
    with jax.default_matmul_precision("highest"):
        for mode in ("dfabric", "gspmd"):
            metrics = train(arch, mesh, mode=mode, batch=batch, seq=seq,
                            steps=steps, dtype="float32")
            curves[mode] = [m["loss"] for m in metrics]
            check_losses(curves[mode], f"{mode} {arch.name}")
            print(f"dp {mode} mesh=(2,2,1) losses {curves[mode]} first step "
                  f"wall {metrics[0]['dt']:.3f} s (compile included)")
    a, b = np.asarray(curves["dfabric"]), np.asarray(curves["gspmd"])
    rel = float(np.max(np.abs(a - b) / np.abs(b)))
    print(f"dp dfabric vs gspmd max relative loss difference {rel:.3e} "
          f"(tolerance {DP_LOSS_RTOL:.0e})")
    if not rel <= DP_LOSS_RTOL:
        raise AssertionError(f"dfabric and gspmd losses disagree: {a} vs {b}")
    return curves


def main(argv: Sequence[str] = ()) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip DFabric-vs-GSPMD phase")
    args = ap.parse_args(list(argv))
    require_tpu(args.chips)
    cache = Path(use_compile_cache())
    dev = device_summary()
    print(f"device kind {dev['kind']} count {dev['count']} "
          f"(using {args.chips})")
    # compile times below are cold only where the cache starts empty
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(f"compile cache {cache}: {warm} entries at start")
    arch = get_arch(ARCH)
    t0 = time.perf_counter()
    if args.chips == 4:
        dp_phase(arch, **DP)
    else:
        train_phase(arch, **TRAIN)
        kernel_phase(KERNEL_WIDTHS)
        decode_phase(arch, **DECODE)
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main(sys.argv[1:])
