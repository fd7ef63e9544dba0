"""The program's side of a dense decoder configuration (Qwen2 / Qwen3).

A configuration file names this module under ``program``; the harness
takes ``arch_of`` from it (``bench/spec.py``).  It maps the file's sizes
onto the program's ``ArchConfig`` and refuses an arch whose mechanisms are
not those that the configuration's reference (``bench/reference/dense.py``)
implements.  It lives apart from the reference, which imports nothing of
the program.
"""
from __future__ import annotations

#: families and mechanisms ``bench/reference/dense.py`` implements
MECHANISMS = dict(family="dense", activation="silu", glu=True,
                  norm="rmsnorm", positional="rope", moe=None,
                  is_encdec=False)


def arch_of(cfg: dict):
    """The program's ``ArchConfig`` with every size of the config file."""
    from repro.configs.base import get_arch

    arch = get_arch(cfg["arch"]).replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], qkv_bias=cfg["attention_bias"],
        qk_norm=cfg["qk_norm"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"], tie_embeddings=cfg["tie_word_embeddings"],
        activation=cfg["hidden_act"])
    for k, want in MECHANISMS.items():
        if getattr(arch, k) != want:
            raise ValueError(f"{cfg['arch']}: {k}={getattr(arch, k)!r}, the "
                             f"reference implements {want!r}")
    return arch
