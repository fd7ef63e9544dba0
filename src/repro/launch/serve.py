"""Serving launcher: batched decode with continuous batching.

Example::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --requests 8 --max-new 16
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.base import get_arch, get_smoke_arch
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import build_model
from repro.models.transformer import ModelSettings
from repro.obs.metrics import MetricsLogger
from repro.runtime.serve_loop import DecodeServer, Request
from repro.utils.jax_compat import make_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--metrics-path", default=None,
                    help="streamed JSONL metrics (repro.obs.metrics)")
    args = ap.parse_args()
    use_compile_cache()

    arch = get_smoke_arch(args.arch) if args.smoke else get_arch(args.arch)
    st = ModelSettings(param_dtype="float32", compute_dtype="float32",
                       remat="none", max_seq=args.max_seq)
    model = build_model(arch, st)
    ndev = len(jax.devices())
    mesh = make_mesh((ndev, 1), ("data", "model"))

    params = model.init(jax.random.key(0))
    metrics = MetricsLogger(path=args.metrics_path, echo=False, run="serve",
                            arch=args.arch)
    server = DecodeServer(model, mesh, batch_slots=args.batch_slots,
                          max_seq=args.max_seq, temperature=args.temperature,
                          metrics=metrics)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        prompt = rng.integers(0, arch.vocab, size=(4,)).astype(np.int32)
        server.submit(Request(uid=i, prompt=prompt, max_new=args.max_new))
    outputs = server.run(params, max_steps=args.max_seq - 1)
    for uid, toks in sorted(outputs.items()):
        print(f"req {uid}: {len(toks)} tokens: {toks[:12]}...")
    print(f"throughput: {server.throughput():.1f} tok/s "
          f"({server.stats['tokens']} tokens, {server.stats['steps']} steps)")
    lat = server.latency_summary()
    if lat:
        print(f"ttft p50 {lat['ttft_p50_s'] * 1e3:.1f} ms "
              f"p99 {lat['ttft_p99_s'] * 1e3:.1f} ms, "
              f"tpot p50 {lat.get('tpot_p50_s', 0) * 1e3:.2f} ms "
              f"p99 {lat.get('tpot_p99_s', 0) * 1e3:.2f} ms")
    metrics.close()


if __name__ == "__main__":
    main()
