"""Config-driven model assembly for all 10 assigned architectures.

One code path covers dense / MoE / VLM LMs; RWKV6, Jamba (hybrid) and
whisper (enc-dec) add their block types.  Layers are stacked and scanned
(`lax.scan` over parameter stacks) so 96-layer models compile fast; the
stack granularity is one *group* (1 layer for uniform archs, one 8-layer
Jamba block for the hybrid).

Modes:
  * train   — full-sequence causal forward, chunked CE loss
  * prefill — forward returning logits of the last position + KV cache
  * decode  — single-token step with explicit cache/state
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import ssm as S

Params = Dict[str, Any]


@dataclass(frozen=True)
class ModelSettings:
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    attn_impl: str = "masked"  # masked | tri | pallas
    attn_block: int = 1024
    attn_chunk: int = 1024
    use_pallas_ssm: bool = False
    remat: str = "full"  # none | full | dots
    scan_layers: bool = True
    loss_chunk: int = 2048
    max_seq: int = 4096  # sizes learned positional tables
    # sequence-parallel residual stream (§Perf): constrain the (B, S, d)
    # activations between blocks to shard S over ``seq_axis`` (and B over
    # ``batch_axes`` in GSPMD mode).  Halves the per-layer TP collective
    # volume (psum -> reduce-scatter + all-gather) and divides the saved
    # scan carry by the TP degree.
    seq_axis: Optional[str] = None
    batch_axes: Optional[Tuple[str, ...]] = None
    # MoE dispatch token groups: routing/cumsum/capacity computed per group
    # so the dispatch gather stays within a DP shard (no cross-pod incast)
    moe_groups: int = 1
    moe_dispatch_dp: Optional[Tuple[str, ...]] = None  # sharding hint for dispatch buffers
    moe_dispatch_tp: Optional[str] = None
    # per-q-head K/V layout for TP-sharded GQA attention (§Perf): the
    # grouped (KV, G) reshape fragments head sharding; repeat keeps it whole
    gqa_repeat: bool = False

    def pdt(self):
        return jnp.dtype(self.param_dtype)

    def cdt(self):
        return jnp.dtype(self.compute_dtype)

    def act_spec(self):
        if self.seq_axis is None and self.batch_axes is None:
            return None
        from jax.sharding import PartitionSpec as P
        b = (tuple(self.batch_axes) if self.batch_axes else None)
        b = b if not (isinstance(b, tuple) and len(b) == 1) else b[0]
        return P(b, self.seq_axis, None)

    def full_seq_spec(self):
        """Layout at attention entry: sequence gathered (replicated over the
        TP axis), batch sharding unchanged — the Megatron-SP gather point."""
        if self.seq_axis is None and self.batch_axes is None:
            return None
        from jax.sharding import PartitionSpec as P
        b = (tuple(self.batch_axes) if self.batch_axes else None)
        b = b if not (isinstance(b, tuple) and len(b) == 1) else b[0]
        return P(b, None, None)


# ---------------------------------------------------------------------------
# Block classification
# ---------------------------------------------------------------------------


def group_size(arch: ArchConfig) -> int:
    """Layers per scanned group."""
    if arch.is_hybrid:
        return arch.attn_every
    return 1


def n_groups(arch: ArchConfig) -> int:
    g = group_size(arch)
    assert arch.n_layers % g == 0, (arch.n_layers, g)
    return arch.n_layers // g


def layer_kind(arch: ArchConfig, layer_id: int) -> str:
    if arch.attn_free:
        return "rwkv"
    if arch.is_hybrid:
        return "attn" if layer_id in set(arch.attn_layer_ids()) else "mamba"
    return "attn"


def layer_is_moe(arch: ArchConfig, layer_id: int) -> bool:
    return layer_id in set(arch.moe_layer_ids())


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------


def _init_layer(arch: ArchConfig, key, layer_id: int, st: ModelSettings) -> Params:
    dt = st.pdt()
    kind = layer_kind(arch, layer_id)
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": L.init_norm(arch, arch.d_model, dt),
                 "ln2": L.init_norm(arch, arch.d_model, dt)}
    if kind == "attn":
        p["attn"] = L.init_attention(arch, ks[0], dt)
    elif kind == "mamba":
        p["mamba"] = S.init_mamba(arch, ks[0], dt)
    elif kind == "rwkv":
        p["tmix"] = S.init_rwkv_time_mix(arch, ks[0], dt)
    if kind == "rwkv":
        p["cmix"] = S.init_rwkv_channel_mix(arch, ks[1], dt)
    elif layer_is_moe(arch, layer_id):
        p["moe"] = L.init_moe(arch, ks[1], dt)
    else:
        p["mlp"] = L.init_mlp(arch, ks[1], dt)
    return p


def _apply_layer(arch: ArchConfig, p: Params, x: jax.Array, positions, mode: str,
                 cache: Optional[Params], st: ModelSettings, layer_id: int,
                 enc_out: Optional[jax.Array] = None,
                 cross_cache: Optional[Params] = None,
                 pos_scalar=None,
                 ) -> Tuple[jax.Array, jax.Array, Optional[Params]]:
    """Returns (x, aux_loss, new_cache)."""
    kind = layer_kind(arch, layer_id)
    aux = jnp.zeros((), jnp.float32)
    new_cache: Optional[Params] = None

    # device-time scopes (op_name metadata, no runtime cost): the token
    # mixer from ln1 to its residual add, then the feed-forward from ln2
    with jax.named_scope("attention" if kind == "attn" else "ssm"):
        h = L.apply_norm(arch, p["ln1"], x)
        if kind == "attn":
            # Megatron-SP gather point: attention consumes the full sequence
            # (replicated over TP); the residual stream stays sequence-sharded.
            fs = st.full_seq_spec()
            if fs is not None and mode == "train":
                h = lax.with_sharding_constraint(h, fs)
            q, k, v = L.attention_qkv(arch, p["attn"], h, positions)
            if mode == "decode":
                kc = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), pos_scalar, axis=1)
                vc = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), pos_scalar, axis=1)
                lens = jnp.full((x.shape[0],), pos_scalar + 1, jnp.int32)
                o = L.attend_decode(q, kc, vc, lens)
                new_cache = {"k": kc, "v": vc}
            else:
                o = L.attend(q, k, v, causal=True, impl=st.attn_impl,
                             block=st.attn_block, q_chunk=st.attn_chunk,
                             kv_chunk=st.attn_chunk, gqa_repeat=st.gqa_repeat)
                if mode == "prefill":
                    new_cache = {"k": k, "v": v}
            attn_out = L.attention_out(p["attn"], o)
            sp = st.act_spec()
            if sp is not None and mode == "train":
                # SP scatter point: the psum of the out-projection becomes a
                # reduce-scatter back onto the sequence-sharded residual.
                attn_out = lax.with_sharding_constraint(attn_out, sp)
            x = x + attn_out
        elif kind == "mamba":
            conv_s = cache.get("conv") if cache else None
            ssm_s = cache.get("ssm") if cache else None
            out, (ncs, nss) = S.apply_mamba(arch, p["mamba"], h, conv_state=conv_s,
                                            ssm_state=ssm_s, use_pallas=st.use_pallas_ssm)
            if mode in ("prefill", "decode"):
                new_cache = {"conv": ncs, "ssm": nss}
            x = x + out
        elif kind == "rwkv":
            shift_s = cache.get("tshift") if cache else None
            wkv_s = cache.get("wkv") if cache else None
            out, (nshift, nwkv) = S.apply_rwkv_time_mix(
                arch, p["tmix"], h, shift_state=shift_s, wkv_state=wkv_s,
                use_pallas=st.use_pallas_ssm)
            if mode in ("prefill", "decode"):
                new_cache = {"tshift": nshift, "wkv": nwkv}
            x = x + out

    # cross attention (whisper decoder)
    with jax.named_scope("attention"):
        if "xattn" in p:
            h = L.apply_norm(arch, p["lnx"], x)
            q = jnp.einsum("bsd,dhk->bshk", h, p["xattn"]["wq"])
            if "bq" in p["xattn"]:
                q = q + p["xattn"]["bq"]
            if mode == "decode":
                kx, vx = cache["xk"], cache["xv"]
            else:
                eo = enc_out
                kx = jnp.einsum("bfd,dhk->bfhk", eo, p["xattn"]["wk"])
                vx = jnp.einsum("bfd,dhk->bfhk", eo, p["xattn"]["wv"])
                if "bk" in p["xattn"]:
                    kx = kx + p["xattn"]["bk"]
                    vx = vx + p["xattn"]["bv"]
            o = L.attend(q, kx, vx, causal=False, impl="masked",
                         q_chunk=st.attn_chunk, kv_chunk=st.attn_chunk)
            x = x + L.attention_out(p["xattn"], o)
            if mode in ("prefill", "decode"):
                new_cache = dict(new_cache or {})
                new_cache["xk"], new_cache["xv"] = kx, vx

    # feed-forward
    with jax.named_scope("mlp"):
        h = L.apply_norm(arch, p["ln2"], x)
        sp = st.act_spec()

        def scatter(out):
            # SP scatter point: the TP psum of the FF down-projection lowers to
            # a reduce-scatter onto the sequence-sharded residual
            if sp is not None and mode == "train":
                return lax.with_sharding_constraint(out, sp)
            return out

        if "cmix" in p:
            shift_s = cache.get("cshift") if cache else None
            out, nshift = S.apply_rwkv_channel_mix(arch, p["cmix"], h, shift_state=shift_s)
            if mode in ("prefill", "decode"):
                new_cache = dict(new_cache or {})
                new_cache["cshift"] = nshift
            x = x + scatter(out)
        elif "moe" in p:
            dsp = None
            if st.moe_dispatch_dp or st.moe_dispatch_tp:
                dp = st.moe_dispatch_dp
                dp = dp if not (isinstance(dp, tuple) and len(dp) == 1) else dp[0]
                dsp = (dp, st.moe_dispatch_tp)
            out, moe_aux = L.apply_moe(arch, p["moe"], h, groups=st.moe_groups,
                                       dispatch_spec=dsp)
            aux = aux + moe_aux
            x = x + scatter(out)
        else:
            x = x + scatter(L.apply_mlp(arch, p["mlp"], h))
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# Groups (scan units)
# ---------------------------------------------------------------------------


def _init_group(arch: ArchConfig, key, group_id: int, st: ModelSettings) -> Params:
    g = group_size(arch)
    ks = jax.random.split(key, g)
    return {f"l{off}": _init_layer(arch, ks[off], group_id * g + off, st)
            for off in range(g)}


def _apply_group(arch: ArchConfig, gp: Params, x, positions, mode, gcache,
                 st: ModelSettings, enc_out=None, pos_scalar=None):
    g = group_size(arch)
    aux = jnp.zeros((), jnp.float32)
    new_gcache: Dict[str, Any] = {}
    for off in range(g):
        lid = off  # within-group offset determines kind (pattern repeats per group)
        lp = gp[f"l{off}"]
        lc = gcache.get(f"l{off}") if gcache else None
        x, a, nc = _apply_layer(arch, lp, x, positions, mode, lc, st, lid,
                                enc_out=enc_out, pos_scalar=pos_scalar)
        aux = aux + a
        if nc is not None:
            new_gcache[f"l{off}"] = nc
    return x, aux, (new_gcache if new_gcache else None)


# NOTE on layer ids inside groups: for uniform archs group_size == 1 and the
# repeating pattern means layer 0's kind/moe-ness matches every layer
# (moe_every divides evenly); for jamba the 8-layer pattern (attn at offset
# 4, MoE at odd offsets) is identical in every group, so using the
# within-group offset as the layer id is exact.


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------


def init_params(arch: ArchConfig, key, st: ModelSettings) -> Params:
    dt = st.pdt()
    ks = jax.random.split(key, 8)
    G = n_groups(arch)
    p: Params = {"embed": L.embed_init(ks[0], (arch.vocab, arch.d_model), dt)}
    gkeys = jax.random.split(ks[1], G)
    p["blocks"] = jax.vmap(lambda k: _init_group(arch, k, 0, st))(gkeys)
    p["final_norm"] = L.init_norm(arch, arch.d_model, dt)
    if not arch.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[2], (arch.d_model, arch.vocab), arch.d_model, dt)
    if arch.positional == "learned":
        p["pos_embed"] = L.embed_init(ks[3], (st.max_seq, arch.d_model), dt)
    if arch.is_encdec:
        ekeys = jax.random.split(ks[4], arch.encoder.n_layers)
        enc_arch = arch  # same dims for whisper
        p["enc_blocks"] = jax.vmap(lambda k: _init_layer(enc_arch, k, 0, st))(ekeys)
        p["enc_final_norm"] = L.init_norm(arch, arch.d_model, dt)
        # decoder layers get cross attention
        xkeys = jax.random.split(ks[5], G)

        def init_x(k):
            return {"xattn": L.init_attention(arch, k, dt),
                    "lnx": L.init_norm(arch, arch.d_model, dt)}
        xp = jax.vmap(init_x)(xkeys)
        # merge into blocks (each group has 1 layer for whisper)
        p["blocks"]["l0"]["xattn"] = xp["xattn"]
        p["blocks"]["l0"]["lnx"] = xp["lnx"]
    return p


# ---------------------------------------------------------------------------
# Encoder (whisper) — frontend is a stub: input is frame embeddings
# ---------------------------------------------------------------------------


def encode(arch: ArchConfig, params: Params, frames: jax.Array,
           st: ModelSettings) -> jax.Array:
    x = frames.astype(st.cdt())
    x = x + L.sinusoidal_positions(x.shape[1], arch.d_model).astype(x.dtype)
    positions = jnp.arange(x.shape[1])

    def body(carry, lp):
        h = L.apply_norm(arch, lp["ln1"], carry)
        q, k, v = L.attention_qkv(arch.replace(positional="none"), lp["attn"], h, positions)
        o = L.attend(q, k, v, causal=False, impl="masked",
                     q_chunk=st.attn_chunk, kv_chunk=st.attn_chunk)
        x2 = carry + L.attention_out(lp["attn"], o)
        h = L.apply_norm(arch, lp["ln2"], x2)
        x2 = x2 + L.apply_mlp(arch, lp["mlp"], h)
        return x2, None

    body_fn = body
    if st.remat != "none":
        body_fn = jax.checkpoint(body, policy=_remat_policy(st))
    x, _ = lax.scan(body_fn, x, params["enc_blocks"])
    return L.apply_norm(arch, params["enc_final_norm"], x)


def _remat_policy(st: ModelSettings):
    if st.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


# ---------------------------------------------------------------------------
# Backbone forward (train / prefill)
# ---------------------------------------------------------------------------


def forward(arch: ArchConfig, params: Params, tokens: jax.Array,
            st: ModelSettings, mode: str = "train",
            frames: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, jax.Array, Optional[Params]]:
    """Returns (hidden (B,S,d), aux_loss, cache-or-None)."""
    B, Sq = tokens.shape
    x = params["embed"][tokens].astype(st.cdt())
    if arch.positional == "learned":
        x = x + params["pos_embed"][:Sq].astype(x.dtype)
    positions = jnp.arange(Sq)[None, :].repeat(B, 0)

    enc_out = None
    if arch.is_encdec:
        assert frames is not None, "enc-dec arch needs frame embeddings"
        enc_out = encode(arch, params, frames, st)

    act_spec = st.act_spec()

    def body(carry, gp):
        x, aux = carry
        if act_spec is not None:
            x = jax.lax.with_sharding_constraint(x, act_spec)
        x2, a, nc = _apply_group(arch, gp, x, positions, mode, None, st,
                                 enc_out=enc_out)
        if act_spec is not None:
            x2 = jax.lax.with_sharding_constraint(x2, act_spec)
        return (x2, aux + a), nc

    body_fn = body
    if st.remat != "none":
        body_fn = jax.checkpoint(body, policy=_remat_policy(st))
    if st.scan_layers:
        (x, aux), caches = lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                                    params["blocks"])
    else:
        aux = jnp.zeros((), jnp.float32)
        caches = []
        G = n_groups(arch)
        for gi in range(G):
            gp = jax.tree.map(lambda a: a[gi], params["blocks"])
            (x, aux), nc = body_fn((x, aux), gp)
            caches.append(nc)
        if mode == "prefill" and caches[0] is not None:
            caches = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
    fs = st.full_seq_spec()
    if fs is not None and mode == "train":
        x = jax.lax.with_sharding_constraint(x, fs)  # gather for the loss
    x = L.apply_norm(arch, params["final_norm"], x)
    return x, aux, (caches if mode == "prefill" else None)


def logits_from_hidden(arch: ArchConfig, params: Params, x: jax.Array) -> jax.Array:
    head = params["embed"].T if arch.tie_embeddings else params["lm_head"]
    return (x @ head.astype(x.dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Loss (chunked over sequence so (B,S,V) logits never materialize)
# ---------------------------------------------------------------------------


def ce_loss_chunked(arch: ArchConfig, params: Params, hidden: jax.Array,
                    labels: jax.Array, st: ModelSettings) -> jax.Array:
    with jax.named_scope("lm_loss"):  # LM head, logsumexp, gold gather
        B, Sq, d = hidden.shape
        chunk = min(st.loss_chunk, Sq)
        assert Sq % chunk == 0
        nch = Sq // chunk
        head = params["embed"].T if arch.tie_embeddings else params["lm_head"]
        h = hidden.reshape(B, nch, chunk, d).swapaxes(0, 1)  # (nch, B, chunk, d)
        y = labels.reshape(B, nch, chunk).swapaxes(0, 1)

        @jax.checkpoint  # logits are recomputed in bwd — never stored per chunk
        def body(acc, hy):
            hc, yc = hy
            logits = (hc @ head.astype(hc.dtype)).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, yc[..., None].clip(0), axis=-1)[..., 0]
            valid = (yc >= 0).astype(jnp.float32)
            nll = (lse - gold) * valid
            return (acc[0] + nll.sum(), acc[1] + valid.sum()), None

        (tot, cnt), _ = lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (h, y))
        return tot / jnp.maximum(cnt, 1.0)


def train_loss(arch: ArchConfig, params: Params, batch: Dict[str, jax.Array],
               st: ModelSettings) -> jax.Array:
    hidden, aux, _ = forward(arch, params, batch["tokens"], st, mode="train",
                             frames=batch.get("frames"))
    loss = ce_loss_chunked(arch, params, hidden, batch["labels"], st)
    if arch.moe is not None:
        loss = loss + 0.01 * aux / max(len(arch.moe_layer_ids()), 1)
    return loss


# ---------------------------------------------------------------------------
# Prefill / decode
# ---------------------------------------------------------------------------


def init_cache(arch: ArchConfig, batch: int, max_seq: int, st: ModelSettings,
               n_frames: Optional[int] = None) -> Params:
    """Empty cache pytree (stacked over groups)."""
    dt = st.cdt()
    KV, hd = arch.n_kv_heads, arch.resolved_head_dim
    G = n_groups(arch)
    g = group_size(arch)

    def layer_cache(off: int):
        kind = layer_kind(arch, off)
        c: Params = {}
        if kind == "attn":
            c = {"k": jnp.zeros((batch, max_seq, KV, hd), dt),
                 "v": jnp.zeros((batch, max_seq, KV, hd), dt)}
        elif kind == "mamba":
            m = arch.mamba
            di = m.expand * arch.d_model
            c = {"conv": jnp.zeros((batch, m.d_conv - 1, di), dt),
                 "ssm": jnp.zeros((batch, di, m.d_state), jnp.float32)}
        elif kind == "rwkv":
            H = arch.d_model // arch.rwkv.head_size
            c = {"tshift": jnp.zeros((batch, arch.d_model), dt),
                 "wkv": jnp.zeros((batch, H, arch.rwkv.head_size, arch.rwkv.head_size), jnp.float32),
                 "cshift": jnp.zeros((batch, arch.d_model), dt)}
        if arch.is_encdec:
            c["xk"] = jnp.zeros((batch, n_frames or arch.encoder.n_frames, KV, hd), dt)
            c["xv"] = jnp.zeros((batch, n_frames or arch.encoder.n_frames, KV, hd), dt)
        return c

    one_group = {f"l{off}": layer_cache(off) for off in range(g)}
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (G,) + a.shape), one_group)


def decode_step(arch: ArchConfig, params: Params, cache: Params,
                tokens: jax.Array, pos: jax.Array, st: ModelSettings
                ) -> Tuple[jax.Array, Params]:
    """One decode step.  tokens: (B, 1) int32; pos: scalar int32 (tokens
    already in cache).  Returns (logits (B, V) fp32, new cache)."""
    B = tokens.shape[0]
    x = params["embed"][tokens].astype(st.cdt())
    if arch.positional == "learned":
        x = x + lax.dynamic_slice_in_dim(params["pos_embed"], pos, 1, axis=0).astype(x.dtype)
    positions = jnp.full((B, 1), pos, jnp.int32)

    def body(carry, gp_gc):
        x, aux = carry
        gp, gc = gp_gc
        cross = None
        x2, a, nc = _apply_group(arch, gp, x, positions, "decode", gc, st,
                                 pos_scalar=pos)
        return (x2, aux + a), nc

    (x, _), new_cache = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                 (params["blocks"], cache))
    x = L.apply_norm(arch, params["final_norm"], x)
    logits = logits_from_hidden(arch, params, x)[:, 0]
    return logits, new_cache


def prefill(arch: ArchConfig, params: Params, tokens: jax.Array,
            st: ModelSettings, frames: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Optional[Params]]:
    """Prefill forward: returns (last-position logits (B, V), cache)."""
    hidden, _, cache = forward(arch, params, tokens, st, mode="prefill",
                               frames=frames)
    logits = logits_from_hidden(arch, params, hidden[:, -1:])[:, 0]
    return logits, cache
