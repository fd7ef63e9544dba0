"""Share of its roofline that the flash-attention kernel reaches: the least
time the chip could take for the calls in the window (the larger of FLOP
over peak FLOP/s and bytes over peak bytes/s, from ``bench.flops``) over
the time its events took on the busiest chip.  Events are found by the
kernel's name; where none ran (the default ``attn_impl="masked"``) there is
nothing to read."""
from bench import flops, trace

KERNEL = "flash_attention"


def read(r):
    if not r.trace.ops:
        return None
    ops = [o for o in r.trace.ops[trace.busiest(r.trace)] if KERNEL in o[0]]
    if not ops:
        return None
    cfg, mix = r.cfg, r.mix
    flop, byts = flops.flash_attention_fwd(
        mix["global_batch"] // r.chips, cfg["num_attention_heads"],
        cfg["num_key_value_heads"], mix["seq_len"], cfg["head_dim"])
    least = max(flop / r.peak["bf16_flops_per_s"], byts / r.peak["hbm_bytes_per_s"])
    return 100.0 * least * len(ops) / (trace.length(trace.union(
        [(s, e) for _, s, e in ops])) / 1e9)
