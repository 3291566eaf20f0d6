"""Operations and bytes the algorithm needs, from the configuration's
shapes alone (never from HLO), so the count stays the same whatever
implements a call.

Conventions: a multiply-add is 2 operations; weights and state are read
from and written to HBM at their served width (float32, 4 bytes); every
call reads the weights it uses once; only logits someone reads are
counted (the last position of a prefill, the pick after an inject, one
per slate decode step); of the vocabulary, its ``vocab_size`` rows.

Per token, per layer:

    Mamba2   in_proj 2 d (2 din + 2 ds + nh), conv 2 cw (din + 2 ds),
             scan 4 nh hp ds (state update + readout), out_proj 2 din d
    dense    q/k/v/o 2 d hd (2 nq + 2 nkv), SwiGLU 6 d dff,
             attention 4 nq hd c over c positions in context
    lm_head  2 d vocab per logits row read
"""
from __future__ import annotations

from typing import Dict

BYTES = 4  # float32 weights and state


def _hd(cfg) -> int:
    return cfg.head_dim or (cfg.d_model // cfg.n_heads if cfg.n_heads else 0)


def layer_params(cfg) -> int:
    """Weights of one layer (norm scales included)."""
    d = cfg.d_model
    if cfg.family == "ssm":
        s = cfg.ssm
        din, nh, ds, cw = cfg.d_inner, cfg.d_inner // s.head_dim, \
            s.d_state, s.conv_width
        return (d * (2 * din + 2 * ds + nh) + cw * (din + 2 * ds)
                + 2 * ds + din + 3 * nh + din + din * d + d)
    hd = _hd(cfg)
    return (d * hd * (2 * cfg.n_heads + 2 * cfg.n_kv_heads)
            + 3 * d * cfg.d_ff + 2 * d)


def weight_bytes(cfg) -> int:
    """Layers, final norm and one vocab_size x d table (tied or head)."""
    return BYTES * (cfg.n_layers * layer_params(cfg) + cfg.d_model
                    + cfg.vocab_size * cfg.d_model)


def token_flops(cfg, ctx: int) -> float:
    """One token through every layer with ``ctx`` positions in context
    (itself included); lm_head not included."""
    d = cfg.d_model
    if cfg.family == "ssm":
        s = cfg.ssm
        din, ds, cw = cfg.d_inner, s.d_state, s.conv_width
        nh = din // s.head_dim
        per = (2 * d * (2 * din + 2 * ds + nh) + 2 * cw * (din + 2 * ds)
               + 4 * nh * s.head_dim * ds + 2 * din * d)
    else:
        hd = _hd(cfg)
        per = (2 * d * hd * (2 * cfg.n_heads + 2 * cfg.n_kv_heads)
               + 6 * d * cfg.d_ff + 4 * cfg.n_heads * hd * ctx)
    return float(cfg.n_layers * per)


def tokens_flops(cfg, start: int, n: int) -> float:
    """Tokens at context lengths start+1 .. start+n."""
    if cfg.family == "ssm":
        return n * token_flops(cfg, 0)
    # attention grows linearly with context: sum in closed form
    base = token_flops(cfg, 0)
    hd = _hd(cfg)
    ctx_sum = n * start + n * (n + 1) // 2
    return n * base + cfg.n_layers * 4 * cfg.n_heads * hd * ctx_sum


def lm_head_flops(cfg) -> float:
    return 2.0 * cfg.d_model * cfg.vocab_size


def state_bytes(cfg, ctx: int) -> int:
    """One row's cached state after ``ctx`` tokens: SSM state and conv
    tails, or ``ctx`` positions of keys and values."""
    if cfg.family == "ssm":
        s = cfg.ssm
        din = cfg.d_inner
        nh = din // s.head_dim
        per = nh * s.head_dim * s.d_state + (s.conv_width - 1) * (
            din + 2 * s.d_state)
    else:
        per = 2 * ctx * cfg.n_kv_heads * _hd(cfg)
    return BYTES * cfg.n_layers * per


def prefill(cfg, b: int, p: int) -> Dict[str, float]:
    """One prefill call: ``b`` rows of ``p`` tokens, last logits read,
    each row's state written."""
    return {"flops": b * (tokens_flops(cfg, 0, p) + lm_head_flops(cfg)),
            "bytes": weight_bytes(cfg) + b * state_bytes(cfg, p)}


def inject(cfg, b: int, s: int, p: int) -> Dict[str, float]:
    """One inject call: ``b`` rows of ``s`` tokens after ``p`` cached
    ones, the pick's logits read; the cached state read and the new
    state written (all of it for SSM, the ``s`` new positions for KV)."""
    new = (state_bytes(cfg, p) if cfg.family == "ssm"
           else state_bytes(cfg, p + s) - state_bytes(cfg, p))
    return {"flops": b * (tokens_flops(cfg, p, s) + lm_head_flops(cfg)),
            "bytes": weight_bytes(cfg) + b * (state_bytes(cfg, p) + new)}


def slate(cfg, b: int, t: int, ctx: int) -> Dict[str, float]:
    """A slate of ``t`` picks: ``t - 1`` decode steps after ``ctx``
    tokens, each reading the weights and every row's state."""
    steps = t - 1
    flops = b * (tokens_flops(cfg, ctx, steps) + steps * lm_head_flops(cfg))
    per_step = [state_bytes(cfg, ctx + j) for j in range(steps)]
    if cfg.family == "ssm":
        rw = sum(2 * x for x in per_step)
    else:
        rw = sum(per_step) + steps * (state_bytes(cfg, 1))
    return {"flops": flops, "bytes": steps * weight_bytes(cfg) + b * rw}


def scatter(cfg, b: int, p: int) -> Dict[str, float]:
    """Admission of ``b`` prefilled rows into pool slots: each row's
    state read once and written once."""
    return {"flops": 0.0, "bytes": 2 * b * state_bytes(cfg, p)}


def row_flops(cfg, p: int, n_sfx: int, t: int, prefilled: bool) -> float:
    """Model operations one served row needs: its ``p`` history tokens
    when it was prefilled, its ``n_sfx`` fresh tokens, ``t - 1`` decode
    steps, and ``t`` logits rows (one per pick)."""
    f = tokens_flops(cfg, p, n_sfx) + tokens_flops(cfg, p + n_sfx, t - 1)
    if prefilled:
        f += tokens_flops(cfg, 0, p)
    return f + t * lm_head_flops(cfg)


def least_time(w: Dict[str, float], peak: Dict[str, float]) -> float:
    """The roofline bound: the larger of operations over peak rate and
    bytes over peak bandwidth, in seconds."""
    return max(w["flops"] / peak["flops_per_s"],
               w["bytes"] / peak["hbm_bytes_per_s"])
