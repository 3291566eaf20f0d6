"""Unified decoder-only model over any ``ModelConfig``.

Layer-stack execution uses **pattern scan**: the per-layer (mixer, mlp) kind
sequence of every assigned arch is periodic — period 1 for uniform stacks,
period 8 for Jamba's attn:mamba 1:7 interleave, period 10 for Granite
4.0-H's 1:9 — so parameters are stored
stacked as ``blocks["pos{p}"]`` with leading dim R = n_layers / P and the
stack runs as a single ``lax.scan`` over R repeats (compile time stays flat
in depth: deepseek-67b's 95 layers lower as 1 scan, not 95 inlined blocks).

Three entry points:
  * ``forward``      — full-sequence logits (training / scoring)
  * ``prefill``      — full-sequence + returns a decode cache
  * ``decode_step``  — ONE token against the cache (serving)

The decode cache is a dict ``{"pos{p}": layer_cache}`` whose leaves carry a
leading R dim; attention layers hold ring-buffer K/V, SSM layers hold
(conv tails, recurrent state). This same cache is what the ITFI serving
engine snapshots for the *batch* feature state and advances incrementally
when fresh events are injected (DESIGN.md §2).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models.common import KeyGen, normal_init
from repro.models.mlp import init_mlp, mlp
from repro.models.moe import init_moe, moe_apply
from repro.models.norms import init_rmsnorm, rmsnorm

NEG_INF = -1e30


# ----------------------------------------------------------------------
# Layer pattern
# ----------------------------------------------------------------------

def block_pattern(cfg: ModelConfig) -> int:
    """Smallest period P with n_layers % P == 0 and kinds[i] == kinds[i % P]."""
    sig = list(zip(cfg.layer_kinds(), cfg.mlp_kinds()))
    n = cfg.n_layers
    for p in range(1, n + 1):
        if n % p == 0 and all(sig[i] == sig[i % p] for i in range(n)):
            return p
    return n


def pattern_sig(cfg: ModelConfig):
    p = block_pattern(cfg)
    sig = list(zip(cfg.layer_kinds(), cfg.mlp_kinds()))
    return sig[:p]


# ----------------------------------------------------------------------
# Init
# ----------------------------------------------------------------------

def _init_layer(kg: KeyGen, cfg: ModelConfig, kind: str, mlp_kind: str,
                dtype) -> Dict[str, Any]:
    p: Dict[str, Any] = {"norm1": init_rmsnorm(kg, cfg.d_model, dtype)}
    if kind == "attn":
        p["attn"] = attn_mod.init_attention(kg, cfg, dtype)
    else:
        p["ssm"] = ssm_mod.init_ssm(kg, cfg, dtype)
    if mlp_kind != "none":
        p["norm2"] = init_rmsnorm(kg, cfg.d_model, dtype)
    if mlp_kind == "dense":
        p["mlp"] = init_mlp(kg, cfg.d_model, cfg.d_ff, dtype)
    elif mlp_kind == "moe":
        p["moe"] = init_moe(kg, cfg, dtype)
    return p


def init_params(cfg: ModelConfig, rng, dtype=jnp.bfloat16) -> Dict[str, Any]:
    kg = KeyGen(rng)
    pat = pattern_sig(cfg)
    P = len(pat)
    R = cfg.n_layers // P
    blocks = {}
    for p, (kind, mlp_kind) in enumerate(pat):
        reps = [_init_layer(kg, cfg, kind, mlp_kind, dtype) for _ in range(R)]
        blocks[f"pos{p}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
    params = {
        "embed": {"table": normal_init(kg(), (cfg.vocab_padded, cfg.d_model),
                                       cfg.d_model ** -0.5, dtype)},
        "blocks": blocks,
        "final_norm": init_rmsnorm(kg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "table": normal_init(kg(), (cfg.vocab_padded, cfg.d_model),
                                 cfg.d_model ** -0.5, dtype)}
    return params


def param_shapes(cfg: ModelConfig, dtype=jnp.bfloat16):
    """Abstract param pytree (no allocation) — used by the dry-run."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k, dtype), jax.ShapeDtypeStruct((2,), jnp.uint32))


# ----------------------------------------------------------------------
# Sublayer application
# ----------------------------------------------------------------------

def _apply_sublayer(lp, x, *, cfg, kind, mlp_kind, mode, cache, positions,
                    valid, prefix_valid, q_chunk, use_kernels, moe_rng,
                    head_pad_to=0, attn_sharding=None, moe_sharding=None):
    """Returns (x, cache_out, aux)."""
    h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
    if kind == "attn":
        with jax.named_scope("attn"):
            if mode == "decode":
                mix, cache_out = attn_mod.attention_decode(
                    lp["attn"], h, positions, cache, cfg)
            else:
                mix, kv = attn_mod.attention_full(
                    lp["attn"], h, positions, cfg, valid=valid,
                    prefix_kv=cache if mode == "extend" else None,
                    prefix_valid=prefix_valid, q_chunk=q_chunk,
                    head_pad_to=head_pad_to, attn_sharding=attn_sharding)
                cache_out = kv if mode in ("prefill", "extend") else None
    else:  # ssm
        with jax.named_scope("ssm"):
            if mode == "decode":
                mix, cache_out = ssm_mod.ssm_decode(lp["ssm"], h, cache, cfg)
            else:
                mix, state = ssm_mod.ssm_forward(
                    lp["ssm"], h, cfg,
                    cache=cache if mode == "extend" else None,
                    use_kernel=use_kernels, valid=valid)
                cache_out = state if mode in ("prefill", "extend") else None
    x = x + _residual(cfg, mix)

    aux = jnp.zeros((), jnp.float32)
    if mlp_kind != "none":
        h = rmsnorm(lp["norm2"], x, cfg.norm_eps)
        if mlp_kind == "dense":
            out = mlp(lp["mlp"], h)
        else:
            # serving (prefill / extend / decode) never drops a token: a
            # cached prefill plus an extend must equal the full prefill
            out, aux = moe_apply(lp["moe"], h, cfg, rng=moe_rng,
                                 moe_sharding=moe_sharding,
                                 drop_free=mode != "forward")
        x = x + _residual(cfg, out)
    return x, cache_out, aux


def _residual(cfg: ModelConfig, branch):
    """A residual branch scaled by ``residual_multiplier`` (no op at 1)."""
    if cfg.residual_multiplier == 1.0:
        return branch
    return branch * cfg.residual_multiplier


# ----------------------------------------------------------------------
# Stack execution
# ----------------------------------------------------------------------

def _run_stack(params, x, *, cfg, mode, caches, positions, valid, q_chunk,
               use_kernels, remat, moe_rng, prefix_valid=None,
               act_sharding=None, head_pad_to=0, attn_sharding=None,
               moe_sharding=None):
    pat = pattern_sig(cfg)
    P = len(pat)
    R = cfg.n_layers // P

    def body(carry, xs):
        x, aux_sum = carry
        block_params, cache_in, rngs = xs
        cache_out = {}
        for p, (kind, mlp_kind) in enumerate(pat):
            key = f"pos{p}"
            x, c_out, aux = _apply_sublayer(
                block_params[key], x, cfg=cfg, kind=kind, mlp_kind=mlp_kind,
                mode=mode, cache=None if cache_in is None else cache_in[key],
                positions=positions, valid=valid, prefix_valid=prefix_valid,
                q_chunk=q_chunk, use_kernels=use_kernels,
                moe_rng=None if rngs is None else rngs[key],
                head_pad_to=head_pad_to, attn_sharding=attn_sharding,
                moe_sharding=moe_sharding)
            if c_out is not None:
                cache_out[key] = c_out
            if act_sharding is not None:
                # keep layer-boundary activations (the remat/scan carries)
                # sharded — this is what bounds live memory at scale
                x = jax.lax.with_sharding_constraint(x, act_sharding)
        return (x, aux_sum + aux), (cache_out if cache_out else None)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)

    rngs = None
    if moe_rng is not None and any(mk == "moe" for _, mk in pat):
        flat = jax.random.split(moe_rng, (R, P))
        rngs = {f"pos{p}": flat[:, p] for p in range(P)}

    xs = (params["blocks"], caches, rngs)
    with jax.named_scope("layers"):
        (x, aux_sum), caches_out = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux_sum, caches_out


# ----------------------------------------------------------------------
# Public entry points
# ----------------------------------------------------------------------

def _embed(params, cfg, tokens, prefix_embeds, embed_mesh=None):
    table = params["embed"]["table"]
    if embed_mesh is None:
        x = table[tokens]  # (B,S_text,d) gather
    else:
        # Explicit shard_map lookup: the table is stored (vocab replicated,
        # d_model sharded over "model"), so the gather is LOCAL per device.
        # XLA's own gather partitioning mis-compiles this pattern inside
        # scanned/remat bodies (hlo-verifier failure), so we don't let it
        # guess. Grad: shard_map transposes to a local scatter-add + psum.
        from jax.sharding import PartitionSpec as PS
        dp = tuple(a for a in ("pod", "data") if a in embed_mesh.axis_names)
        dpn = 1
        for a in dp:
            dpn *= embed_mesh.shape[a]
        bspec = dp if tokens.shape[0] % dpn == 0 else None
        tpn = embed_mesh.shape.get("model", 1)
        dspec = "model" if cfg.d_model % tpn == 0 else None
        x = jax.shard_map(
            lambda tbl, tok: tbl[tok], mesh=embed_mesh,
            in_specs=(PS(None, dspec), PS(bspec, None)),
            out_specs=PS(bspec, None, dspec))(table, tokens)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    return x


@jax.named_scope("lm_head")
def _logits(params, cfg: ModelConfig, x, head_sharding=None):
    table = (params["embed"]["table"] if cfg.tie_embeddings
             else params["lm_head"]["table"])
    if head_sharding is not None:
        # reshard the (tied) table to vocab-sharded for the head matmul so
        # logits come out vocab-sharded (cheap: table bytes ≪ logits bytes)
        table = jax.lax.with_sharding_constraint(table, head_sharding)
    logits = jnp.einsum("bsd,vd->bsv", x, table).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.vocab_padded != cfg.vocab_size:
        vmask = jnp.arange(cfg.vocab_padded) < cfg.vocab_size
        logits = jnp.where(vmask[None, None, :], logits, NEG_INF)
    return logits


def _default_positions(tokens, prefix_embeds):
    b = tokens.shape[0]
    s = tokens.shape[1] + (prefix_embeds.shape[1] if prefix_embeds is not None else 0)
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))


def forward(params, cfg: ModelConfig, tokens, *, prefix_embeds=None,
            positions=None, valid=None, q_chunk: int = 512,
            use_kernels: bool = False, remat: bool = False, moe_rng=None,
            act_sharding=None, logits_sharding=None, head_sharding=None,
            embed_mesh=None, head_pad_to=0, attn_sharding=None,
            moe_sharding=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence logits. Returns (logits (B,S,Vp) fp32, moe aux loss)."""
    x = _embed(params, cfg, tokens, prefix_embeds, embed_mesh)
    if positions is None:
        positions = _default_positions(tokens, prefix_embeds)
    x, aux, _ = _run_stack(
        params, x, cfg=cfg, mode="forward", caches=None, positions=positions,
        valid=valid, q_chunk=q_chunk, use_kernels=use_kernels, remat=remat,
        moe_rng=moe_rng, act_sharding=act_sharding, head_pad_to=head_pad_to,
        attn_sharding=attn_sharding, moe_sharding=moe_sharding)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, cfg, x, head_sharding)
    if logits_sharding is not None:
        logits = jax.lax.with_sharding_constraint(logits, logits_sharding)
    return logits, aux


def prefill(params, cfg: ModelConfig, tokens, *, prefix_embeds=None,
            positions=None, valid=None, q_chunk: int = 512,
            use_kernels: bool = False, act_sharding=None,
            head_sharding=None, logits_last_only: bool = False,
            embed_mesh=None, head_pad_to=0, attn_sharding=None,
            moe_sharding=None) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Full-sequence pass that also returns the decode cache (per-layer K/V
    for attention positions, conv/state for SSM positions).

    ``logits_last_only``: serving prefill only needs the next-token logits —
    skipping the (B,S,Vp) materialization is a large memory/compute saving
    at 32k prefill."""
    x = _embed(params, cfg, tokens, prefix_embeds, embed_mesh)
    if positions is None:
        positions = _default_positions(tokens, prefix_embeds)
    x, _, caches = _run_stack(
        params, x, cfg=cfg, mode="prefill", caches=None, positions=positions,
        valid=valid, q_chunk=q_chunk, use_kernels=use_kernels, remat=False,
        moe_rng=None, act_sharding=act_sharding, head_pad_to=head_pad_to,
        attn_sharding=attn_sharding, moe_sharding=moe_sharding)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if logits_last_only:
        x = x[:, -1:]
    return _logits(params, cfg, x, head_sharding), caches


def extend(params, cfg: ModelConfig, caches, tokens, start_pos, *,
           valid=None, prefix_valid=None, q_chunk: int = 512,
           use_kernels: bool = False) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Incremental prefill: run only the ``tokens`` suffix against an
    existing prefill cache (the KV/state snapshot of the *batch* history).

    This is the TPU-native form of the paper's inference-time injection —
    fresh events cost O(suffix), not O(full history) (DESIGN.md §2).

    tokens (B,Ss); start_pos (B,) = prefix length per row. Returns
    (logits over suffix positions, caches covering prefix+suffix).
    """
    x = _embed(params, cfg, tokens, None)
    b, ss = tokens.shape
    positions = start_pos[:, None] + jnp.arange(ss, dtype=jnp.int32)[None, :]
    x, _, caches_out = _run_stack(
        params, x, cfg=cfg, mode="extend", caches=caches, positions=positions,
        valid=valid, prefix_valid=prefix_valid, q_chunk=q_chunk,
        use_kernels=use_kernels, remat=False, moe_rng=None)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x), caches_out


def decode_step(params, cfg: ModelConfig, caches, tokens, pos,
                ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """ONE-token serve step. tokens (B,1) int32; pos (B,) int32 = number of
    tokens already in the cache (the new token's absolute position)."""
    x = _embed(params, cfg, tokens, None)
    x, _, caches_out = _run_stack(
        params, x, cfg=cfg, mode="decode", caches=caches, positions=pos,
        valid=None, q_chunk=1, use_kernels=False, remat=False, moe_rng=None)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x), caches_out


# ----------------------------------------------------------------------
# Cache construction
# ----------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, capacity: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Fresh (empty) decode cache. ``capacity`` = KV slots for attention
    layers (clamped to the sliding window when the arch has one)."""
    pat = pattern_sig(cfg)
    R = cfg.n_layers // len(pat)
    cap = min(capacity, cfg.sliding_window) if cfg.sliding_window else capacity
    caches = {}
    for p, (kind, _) in enumerate(pat):
        if kind == "attn":
            one = attn_mod.init_kv_cache(cfg, batch, cap, dtype)
        else:
            one = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        caches[f"pos{p}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), one)
    return caches


def cache_shapes(cfg: ModelConfig, batch: int, capacity: int,
                 dtype=jnp.bfloat16):
    """Abstract cache pytree for the dry-run (no allocation)."""
    return jax.eval_shape(lambda: init_cache(cfg, batch, capacity, dtype))


def cache_from_prefill(cfg: ModelConfig, caches, capacity: int,
                       valid=None) -> Dict[str, Any]:
    """Convert prefill per-layer outputs into a ring decode cache.

    ``valid`` (B,S): the prefill pad mask — left-padded slots stay masked
    in the ring cache so decode never attends them."""
    pat = pattern_sig(cfg)
    cap = min(capacity, cfg.sliding_window) if cfg.sliding_window else capacity
    out = {}
    for p, (kind, _) in enumerate(pat):
        key = f"pos{p}"
        if kind == "attn":
            out[key] = jax.vmap(
                lambda kv: attn_mod.cache_from_prefill(kv, cap, valid)
            )(caches[key])
        else:
            out[key] = caches[key]
    return out
