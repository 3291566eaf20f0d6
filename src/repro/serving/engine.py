"""TPU serving engine — the ITFI inference flow as cache operations.

The paper's injection maps onto TPU serving as **incremental prefill**
(DESIGN.md §2): the batch features correspond to a cached model state
(KV cache for attention layers, recurrent state for SSM layers) that the
daily job can materialize; injecting fresh events only runs the *suffix*
through the model — O(Δ) cost instead of O(full history):

    snapshot = engine.prefill(batch_history)        # daily job, cacheable
    state    = engine.inject(snapshot, fresh_events)  # per-request, cheap
    logits   = engine.decode(state, token, pos)       # unchanged serving

``prefill``/``inject`` return *sequence-form* caches (K/V grown along the
sequence dim; SSM conv tails + state); ``finalize`` converts to the
fixed-capacity ring cache that ``decode`` uses. All entry points are jit'd
once per shape; the engine pads requests to fixed shapes.

**Sharded serving** (the multi-device path): pass a ``Mesh`` and the
engine resolves the full `sharding/rules.py` serving bundle once —
parameters land replicated over the data axes and TP-sharded over the
model axis (decode-mode layout, FSDP stripped — see
``rules.serving_pspecs``), request panes shard over the data axes
(``max_batch`` must divide the data-axis size; checked at construction,
never discovered as an uneven-sharding error inside jit), and every
entry point is jit'd with explicit ``in_shardings`` /
``out_shardings`` so prefill/inject/decode caches stay resident in their
sharded layout between calls. The ring KV/SSM cache is **donated** into
``decode`` — its input and output are shape- and sharding-identical, so
each serve step updates the cache in place instead of doubling its
footprint (inject/finalize change buffer shapes, seq-grow and seq→ring,
so their inputs cannot alias and are not donated — XLA frees them at the
end of the call anyway). On CPU test meshes donation is a no-op; on TPU
it is the difference between one decode-cache working set and two.

**The served weights.** Where a float32 matmul at the precision in effect
is one bfloat16 pass (a TPU at its default precision), the chip rounds
each float32 weight to bfloat16 before every product, and a program that
reads the float32 tree repeats that conversion on every call (the slate
converts all of them before its decode loop). So the engine holds a
served copy of the tree in which each float32 leaf of ``MATMUL_LEAVES``
is rounded to bfloat16 once, at construction (and, for the leaves a
patch replaces, in ``apply_patch``), on the leaf's own sharding. The
products see exactly the values they saw before; only the conversion
pass and half the weight bytes are gone. ``params`` stays the float32
tree the caller handed in. Elsewhere -- on the CPU, under
``jax.default_matmul_precision("highest")``, or for a bfloat16 tree --
the programs read ``params`` itself, and a call made under a precision
other than the one-pass default reads ``params`` too.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.model import (cache_from_prefill, decode_step, extend,
                                init_cache, prefill)
from repro.serving.tracing import span

# Parameter leaves (by key) that the served programs read only as an
# operand of a default-precision matmul: the SSD projections (models/ssm.py),
# the attention projections (attention.py), the SwiGLU, expert, shared
# expert and router weights (mlp.py, moe.py), and an untied head's table.
# Conv taps, biases, norm scales, A_log, D, dt_bias and the embedding
# table are read otherwise (elementwise, or by a gather) and keep float32.
MATMUL_LEAVES = frozenset({
    "wz", "wx", "wB", "wC", "wdt", "out_proj",
    "wq", "wk", "wv", "wo",
    "gate", "up", "down", "router", "shared_gate", "shared_up", "shared_down"})
# values of ``jax_default_matmul_precision`` at which a float32 matmul is
# one bfloat16 pass on a TPU
ONE_PASS = (None, "default", "bfloat16", "fastest")


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    max_batch: int = 8
    prefill_len: int = 1024        # padded batch-history length
    inject_len: int = 32           # padded fresh-suffix length
    cache_capacity: int = 2048     # ring-cache slots for decode
    temperature: float = 0.0       # 0 = greedy
    q_chunk: int = 512


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, scfg: ServingConfig,
                 mesh: Optional[Mesh] = None):
        self.cfg = cfg
        self.scfg = scfg
        self.mesh = mesh
        self._slate_fns: Dict[int, Any] = {}
        pf = _named_partial(_prefill_impl, cfg=cfg, q_chunk=scfg.q_chunk)
        inj = _named_partial(_inject_impl, cfg=cfg, q_chunk=scfg.q_chunk)
        fin = _named_partial(_finalize_impl, cfg=cfg,
                             capacity=scfg.cache_capacity)
        dec = _named_partial(_decode_impl, cfg=cfg)
        self._platform = _platform(mesh)
        if mesh is None:
            self.data_shards = 1
            self.params = params
            self.served_params = self._serve(params)
            self._tok_ns = self._row_ns = self._seq_ns = self._ring_ns = None
            self._prefill = jax.jit(pf)
            self._inject = self._inject_fb = jax.jit(inj)
            self._finalize = jax.jit(fin)
            self._decode = jax.jit(dec)
            return

        from repro.sharding.rules import serving_pspecs
        sp = serving_pspecs(cfg, mesh, scfg.max_batch)
        self.data_shards = sp.data_shards
        ns = lambda spec: jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec,
            is_leaf=lambda x: isinstance(x, P))
        p_ns, tok_ns, row_ns = ns(sp.params), ns(sp.tokens), ns(sp.rows)
        seq_ns, ring_ns, lg_ns = (ns(sp.seq_caches), ns(sp.ring_caches),
                                  ns(sp.logits))
        # Entry points re-place operands with device_put (below): jit
        # in_shardings only *check* committed arrays, they don't reshard
        # them — and the serving scheduler legitimately hands us host-assembled
        # states (per-user LRU rows concatenated into a pane).
        self._tok_ns, self._row_ns = tok_ns, row_ns
        self._seq_ns, self._ring_ns = seq_ns, ring_ns
        self._param_ns = p_ns
        # Parameters move to their sharded layout ONCE, here — every jit
        # below then sees them already placed (no per-call transfer).
        self.params = jax.device_put(params, p_ns)
        self.served_params = self._serve(self.params)
        # in_shardings double as device_put: numpy panes from pad_tokens
        # and host-assembled cache states get scattered to the mesh at the
        # call boundary; out_shardings pin the returned caches to the same
        # layout the next entry point consumes, so nothing round-trips.
        self._prefill = jax.jit(
            pf, in_shardings=(p_ns, tok_ns, tok_ns),
            out_shardings=(lg_ns, seq_ns))
        inj_out = {"caches": seq_ns, "logits": lg_ns, "valid": tok_ns,
                   "next_pos": row_ns, "n_valid": row_ns,
                   "last_valid_logits": tok_ns}
        self._inject = jax.jit(
            inj,
            in_shardings=(p_ns, seq_ns, tok_ns, tok_ns, tok_ns, row_ns),
            out_shardings=inj_out)
        self._inject_fb = jax.jit(
            inj,
            in_shardings=(p_ns, seq_ns, tok_ns, tok_ns, tok_ns, row_ns,
                          tok_ns),
            out_shardings={**inj_out, "first_logits": tok_ns})
        self._finalize = jax.jit(
            fin, in_shardings=(seq_ns, tok_ns), out_shardings=ring_ns)
        self._decode = jax.jit(
            dec, in_shardings=(p_ns, ring_ns, tok_ns, row_ns),
            out_shardings=(lg_ns, ring_ns), donate_argnums=(1,))

    # ------------------------------------------------------------------
    @property
    def rounded_weight_bytes(self) -> int:
        """Bytes held by the served copy's rounded (bfloat16) leaves: 0
        where the programs read ``params`` itself."""
        return sum(int(s.nbytes) for s, p in zip(
            jax.tree.leaves(self.served_params),
            jax.tree.leaves(self.params)) if s is not p)

    def _serve(self, params, patched=()):
        """The tree the programs read at one-pass precision: ``params``
        with each float32 leaf of ``MATMUL_LEAVES`` rounded to bfloat16, by
        one program, on the leaf's own sharding; ``params`` itself where
        the chip does not round float32 products or nothing is float32.
        ``patched`` (indices of leaves of ``params``) rounds only those
        leaves again and keeps the rest of the current copy."""
        if not patched and not _one_pass(self._platform):
            return params
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        leaves = [x for _, x in flat]
        if patched:
            old = jax.tree.leaves(self.served_params)
            leaves = [x if i in patched else old[i]
                      for i, x in enumerate(leaves)]
        todo = [i for i, (path, x) in enumerate(flat)
                if (not patched or i in patched) and x.dtype == jnp.float32
                and _matmul_only(path)]
        if not todo and not patched:
            return params
        for i, y in zip(todo, _rounded([leaves[i] for i in todo])):
            leaves[i] = y
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _weights(self):
        """The served copy where the precision in effect rounds float32
        products to bfloat16, else the float32 ``params``."""
        return (self.served_params if _one_pass(self._platform)
                else self.params)

    # ------------------------------------------------------------------
    def prefill_state_shapes(self) -> Tuple[Any, Any]:
        """Abstract ``(logits, caches)`` of one prefill pane — the shapes
        and dtypes ``prefill`` would return for a ``(max_batch,
        prefill_len)`` call — derived via ``jax.eval_shape`` without
        running (or even compiling) the model. The paged state pool
        (serving/pool.py) sizes its slot buffers from this, so pool
        preallocation can never drift from what prefill actually
        produces."""
        b, p = self.scfg.max_batch, self.scfg.prefill_len
        pf = _named_partial(_prefill_impl, cfg=self.cfg,
                            q_chunk=self.scfg.q_chunk)
        pshapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.params)
        return jax.eval_shape(pf, pshapes,
                              jax.ShapeDtypeStruct((b, p), jnp.int32),
                              jax.ShapeDtypeStruct((b, p), jnp.bool_))

    # ------------------------------------------------------------------
    def apply_patch(self, leaves: Dict[str, Any]) -> int:
        """Install new values for a subset of parameter leaves.

        ``leaves`` maps ``jax.tree_util.keystr`` paths (the convention
        ``training/online.py`` emits) to full replacement arrays. O(patch):
        only the named leaves are validated, transferred (re-placed to
        their sharded layout on a mesh) and rebound; every other leaf
        object is reused as-is, and ``self.params`` swaps in one tree
        rebind — the caller (``Gateway.install_patch``) decides *when*
        that rebind is safe (between panes). Shapes and dtypes must match
        the current tree exactly: the jitted entry points were traced
        against them, and a silent mismatch would mean recompilation (or
        wrong math) mid-serving. The served copy rounds the patched
        matmul leaves again, and only those. Returns the number of leaves
        patched.
        """
        if not leaves:
            return 0
        rounds = self.served_params is not self.params
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.params)
        by_path = {jax.tree_util.keystr(p): i for i, (p, _) in
                   enumerate(flat)}
        ns_leaves = (jax.tree.leaves(self._param_ns)
                     if self.mesh is not None else None)
        new_leaves = [leaf for _, leaf in flat]
        for key, val in leaves.items():
            i = by_path.get(key)
            if i is None:
                raise KeyError(
                    f"patch leaf {key!r} is not in the parameter tree")
            old = new_leaves[i]
            arr = jnp.asarray(val)
            if tuple(arr.shape) != tuple(old.shape):
                raise ValueError(
                    f"patch leaf {key!r}: shape {tuple(arr.shape)} != "
                    f"{tuple(old.shape)}")
            if arr.dtype != old.dtype:
                raise ValueError(
                    f"patch leaf {key!r}: dtype {arr.dtype} != "
                    f"{old.dtype}")
            new_leaves[i] = (jax.device_put(arr, ns_leaves[i])
                            if ns_leaves is not None else arr)
        self.params = jax.tree_util.tree_unflatten(treedef, new_leaves)
        self.served_params = (
            self._serve(self.params, patched={by_path[k] for k in leaves})
            if rounds else self.params)
        return len(leaves)

    # ------------------------------------------------------------------
    def pad_tokens(self, seqs, length: int, align: str = "right",
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Pad a list of variable-length token lists into (tokens, valid)
        of shape (max_batch, length).

        Prefill buffers are right-aligned (real tokens end at the last
        buffer position, so one uniform ``next_pos`` covers the batch);
        inject suffixes are LEFT-aligned (real tokens contiguous from the
        row's ``next_pos`` — RoPE distances stay exact per row).

        Raises ``ValueError`` when more than ``max_batch`` sequences are
        passed — silently dropping requests is a serving bug; callers with
        larger waves must pane-split (see serving/scheduler.py).
        """
        b = self.scfg.max_batch
        if len(seqs) > b:
            raise ValueError(
                f"{len(seqs)} sequences exceed max_batch={b}; split the "
                f"request wave into panes of at most {b} rows")
        with span("repro.feature.tokens"):
            toks = np.zeros((b, length), np.int32)
            valid = np.zeros((b, length), bool)
            for i, s in enumerate(seqs):
                s = list(s)[-length:]
                if not s:
                    continue
                if align == "right":
                    toks[i, length - len(s):] = s
                    valid[i, length - len(s):] = True
                else:
                    toks[i, :len(s)] = s
                    valid[i, :len(s)] = True
            return toks, valid

    # ------------------------------------------------------------------
    def _place(self, x, ns):
        """Reshard ``x`` to its serving layout (no-op off-mesh / already
        placed). device_put, not in_shardings: committed arrays — LRU rows
        concatenated host-side into a pane — need an actual transfer."""
        if self.mesh is None or x is None:
            return x
        return jax.device_put(x, ns)

    # ------------------------------------------------------------------
    def prefill(self, tokens, valid) -> Dict[str, Any]:
        """Materialize the batch-history state (the daily-job analogue).

        Positions index the padded buffer (real tokens right-aligned), so
        subsequent inject/decode positions continue at ``buf_len`` —
        relative distances between real tokens are exact under RoPE.
        """
        with span("repro.engine.prefill"):
            tokens = self._place(jnp.asarray(tokens), self._tok_ns)
            valid = self._place(jnp.asarray(valid), self._tok_ns)
            logits, caches = self._prefill(self._weights(), tokens, valid)
            b, s = tokens.shape
            return {"caches": caches, "valid": valid,
                    # right-aligned prefill: every row's next position is S
                    "next_pos": jnp.full((b,), s, jnp.int32),
                    "logits": logits}

    def inject(self, state: Dict[str, Any], suffix_tokens, suffix_valid,
               fallback_logits=None) -> Dict[str, Any]:
        """Incremental prefill of fresh events against a cached state —
        the paper's injection: O(suffix) compute, model untouched.
        Suffix must be LEFT-aligned (see pad_tokens).

        All state bookkeeping (valid concat, next_pos advance, per-row
        last-*valid*-position logit extraction) happens inside the jit —
        eager follow-up ops on the sharded outputs were a measurable
        serve-path cost. Extra keys vs prefill state: ``n_valid`` (real
        suffix length per row) and ``last_valid_logits`` (the next-item
        scores after the row's final real event). When
        ``fallback_logits`` (B, Vp) is given — the pre-inject scores —
        the result also carries ``first_logits``: last-valid scores for
        rows with a real suffix, the fallback for empty rows."""
        with span("repro.engine.inject"):
            args = [self._weights(),
                    self._place(state["caches"], self._seq_ns),
                    self._place(jnp.asarray(suffix_tokens), self._tok_ns),
                    self._place(jnp.asarray(suffix_valid), self._tok_ns),
                    self._place(state["valid"], self._tok_ns),
                    self._place(state["next_pos"], self._row_ns)]
            if fallback_logits is None:
                return self._inject(*args)
            return self._inject_fb(
                *args, self._place(jnp.asarray(fallback_logits),
                                   self._tok_ns))

    def finalize(self, state: Dict[str, Any]) -> Dict[str, Any]:
        """Sequence-form state -> fixed-capacity ring cache for decode."""
        with span("repro.engine.finalize"):
            caches = self._finalize(
                self._place(state["caches"], self._seq_ns),
                self._place(state["valid"], self._tok_ns))
            return {"caches": caches,
                    "pos": self._place(state["next_pos"], self._row_ns)}

    def decode(self, dec: Dict[str, Any], tokens) -> Tuple[jnp.ndarray, Dict]:
        """One serve step: tokens (B,1) -> (logits (B,Vp), updated dec)."""
        logits, caches = self._decode(
            self._weights(),
            self._place(dec["caches"], self._ring_ns),
            self._place(jnp.asarray(tokens), self._tok_ns),
            self._place(dec["pos"], self._row_ns))
        return logits[:, 0], {"caches": caches, "pos": dec["pos"] + 1}

    def decode_slate(self, state: Dict[str, Any], first_logits,
                     slate_len: int, row_lens=None, wait: bool = True,
                     ) -> np.ndarray | PendingSlate:
        """finalize + a greedy distinct-item slate in ONE jit call.

        The per-token python loop (mask → argmax → decode → sync) used to
        dominate the serve hot path with eager-op dispatch; here the whole
        slate runs as a ``lax.scan`` over ``slate_len - 1`` decode steps
        with the already-chosen mask kept on device. Greedy only: a
        ``temperature > 0`` engine raises rather than silently serving
        greedy slates (sampled slate decode is not implemented).
        Returns int32 (B, slate_len); each row's items are distinct.

        ``row_lens`` (B,) enables **per-request slate lengths** inside a
        fixed-shape pane: the pane still decodes ``slate_len`` (the pane
        max) steps as one traced program, but every row's slots at
        ``>= row_lens[row]`` are masked to -1 inside the jit. The first
        ``row_lens[row]`` items of a row are bitwise identical to what a
        ``slate_len=row_lens[row]`` decode of that row would have chosen
        (greedy decode is a prefix-stable sequence), so callers just
        slice. ``row_lens`` is a traced operand — one compiled program
        serves every mix of lengths at a given pane max.

        ``wait=False`` returns as soon as the slate is launched, as a
        :class:`PendingSlate` that ``np.asarray`` reads back: the caller
        can launch the next pane's programs first, so the device has them
        queued when this slate ends.
        """
        if self.scfg.temperature > 0:
            raise NotImplementedError(
                "decode_slate is greedy-only; sampled slate decode "
                f"(temperature={self.scfg.temperature}) is not implemented "
                "— drive decode()/sample() directly for sampled serving")
        dec = self.finalize(state)
        with span("repro.engine.slate"):
            key = slate_len if row_lens is None else ("masked", slate_len)
            fn = self._slate_fns.get(key)
            if fn is None:
                body = _slate_impl if row_lens is None else _slate_masked_impl
                impl = _named_partial(body, cfg=self.cfg,
                                      slate_len=slate_len)
                if self.mesh is None:
                    fn = jax.jit(impl)
                elif row_lens is None:
                    fn = jax.jit(impl, in_shardings=(
                        self._param_ns, self._ring_ns, self._row_ns,
                        self._tok_ns), out_shardings=self._tok_ns)
                else:
                    fn = jax.jit(impl, in_shardings=(
                        self._param_ns, self._ring_ns, self._row_ns,
                        self._tok_ns, self._row_ns),
                        out_shardings=self._tok_ns)
                self._slate_fns[key] = fn
            first = self._place(jnp.asarray(first_logits), self._tok_ns)
            if row_lens is None:
                slate = fn(self._weights(), dec["caches"], dec["pos"], first)
            else:
                lens = self._place(jnp.asarray(row_lens, jnp.int32),
                                   self._row_ns)
                slate = fn(self._weights(), dec["caches"], dec["pos"],
                           first, lens)
        pending = PendingSlate(slate)
        return np.asarray(pending) if wait else pending

    def sample(self, logits, rng=None) -> jnp.ndarray:
        if self.scfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            rng, logits / self.scfg.temperature, axis=-1).astype(jnp.int32)


class PendingSlate:
    """A slate launched by ``decode_slate(wait=False)`` and not read back.

    ``np.asarray(p)`` waits for the device and reads the int32 (B,
    slate_len) slate back under the ``repro.engine.readback`` span;
    ``p.copy()`` does the same into a writable array. Reading twice
    gives the same values."""

    def __init__(self, slate: jax.Array):
        self._slate = slate

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        with span("repro.engine.readback"):
            out = np.asarray(self._slate, dtype)
        return out.copy() if copy else out

    def copy(self) -> np.ndarray:
        return np.asarray(self).copy()


# ----------------------------------------------------------------------
# jit bodies (pure functions of pytrees + static cfg)
# ----------------------------------------------------------------------

def _platform(mesh: Optional[Mesh]) -> str:
    """The platform the engine's programs run on."""
    dev = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    return dev.platform


def _one_pass(platform: str) -> bool:
    """Whether a float32 matmul at the precision in effect is one
    bfloat16 pass on ``platform``: a TPU's default precision."""
    return (platform == "tpu" and
            jax.config.jax_default_matmul_precision in ONE_PASS)


def _matmul_only(path) -> bool:
    """Whether the leaf at ``path`` is one of ``MATMUL_LEAVES``, or an
    untied head's table (the tied one is also the embedding lookup's)."""
    keys = [getattr(k, "key", None) for k in path]
    return keys[-1] in MATMUL_LEAVES or keys[-2:] == ["lm_head", "table"]


def _to_bf16(xs):
    return [x.astype(jnp.bfloat16) for x in xs]


def _rounded(xs):
    """bfloat16 copies of the arrays ``xs`` by one program, each on the
    sharding of the array it copies."""
    if not xs:
        return []
    return jax.jit(_to_bf16, out_shardings=[
        getattr(x, "sharding", None) for x in xs])(xs)


def _named_partial(fn, **static):
    """``fn`` with its static arguments bound, keeping ``fn``'s name: XLA
    names the compiled program ``jit_<name>`` (``jit__inject_impl``), where
    a bare ``functools.partial`` comes out as ``jit__unknown``."""
    return functools.update_wrapper(functools.partial(fn, **static), fn)


def _prefill_impl(params, tokens, valid, *, cfg, q_chunk):
    return prefill(params, cfg, tokens, valid=valid, q_chunk=q_chunk)


def _inject_impl(params, caches, tokens, valid, prefix_valid, start,
                 fallback_logits=None, *, cfg, q_chunk):
    logits, caches = extend(params, cfg, caches, tokens, start,
                            valid=valid, prefix_valid=prefix_valid,
                            q_chunk=q_chunk)
    n_valid = valid.sum(-1).astype(jnp.int32)
    # logits at each row's last REAL suffix position (left-aligned
    # suffixes: position n_valid - 1; clamped for empty rows, whose value
    # is meaningless — callers gate on n_valid > 0). Selected by one-hot
    # contraction, not logits[rows, idx]: a batch-dependent gather makes
    # GSPMD all-gather the whole (B,Ss,V) logits across the data axis.
    # HIGHEST keeps the selection an exact copy on the TPU, whose default
    # float32 matmul is one bfloat16 pass.
    with jax.named_scope("select"):
        sel = (jnp.arange(logits.shape[1], dtype=jnp.int32)[None, :]
               == jnp.maximum(n_valid - 1, 0)[:, None])
        last_valid = jnp.einsum("bs,bsv->bv", sel.astype(logits.dtype),
                                logits, precision=jax.lax.Precision.HIGHEST)
    out = {
        "caches": caches, "logits": logits,
        "valid": jnp.concatenate([prefix_valid, valid], axis=1),
        "next_pos": start + n_valid,
        "n_valid": n_valid,
        "last_valid_logits": last_valid,
    }
    if fallback_logits is not None:
        # next-item scores per row: after the last real fresh event, or
        # the caller-supplied pre-inject scores when the row's suffix is
        # empty — computed here so the serve loop never syncs logits
        with jax.named_scope("select"):
            out["first_logits"] = jnp.where(
                (n_valid > 0)[:, None], last_valid, fallback_logits)
    return out


def _finalize_impl(caches, valid, *, cfg, capacity):
    return cache_from_prefill(cfg, caches, capacity, valid=valid)


def _decode_impl(params, caches, tokens, pos, *, cfg):
    return decode_step(params, cfg, caches, tokens, pos)


def _slate_impl(params, caches, pos, first, *, cfg, slate_len):
    """Greedy slate of ``slate_len`` distinct items as one traced loop.

    Matches the retired host loop operation-for-operation: pick from the
    current logits with already-chosen items masked, then advance decode —
    ``slate_len - 1`` decode steps total (the last pick needs no advance).
    """
    vocab_iota = jnp.arange(first.shape[-1], dtype=jnp.int32)

    @jax.named_scope("pick")
    def pick(logits, mask):
        tok = jnp.argmax(jnp.where(mask, -1e30, logits),
                         axis=-1).astype(jnp.int32)
        # mark via one-hot compare, NOT a scatter: a scatter's indices
        # force GSPMD to all-gather inside the decode loop (a cross-device
        # sync per step); the compare partitions cleanly over the batch
        return tok, mask | (vocab_iota[None, :] == tok[:, None])

    def step(carry, _):
        caches, pos, logits, mask = carry
        tok, mask = pick(logits, mask)
        nxt, caches = decode_step(params, cfg, caches, tok[:, None], pos)
        return (caches, pos + 1, nxt[:, 0], mask), tok

    mask0 = jnp.zeros(first.shape, bool)
    (_, _, logits, mask), toks = jax.lax.scan(
        step, (caches, pos, first, mask0), None, length=slate_len - 1)
    last, _ = pick(logits, mask)
    return jnp.concatenate(
        [jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)


def _slate_masked_impl(params, caches, pos, first, row_lens, *, cfg,
                       slate_len):
    """Per-request slate lengths on a fixed-shape pane: decode the pane
    max, then mask each row's tail (slots >= row_lens[row]) to -1. The
    mask is a compare against an iota — no batch-dependent scatter, so
    the partitioned program stays collective-free like the uniform one.
    Greedy decode picks each item from state that only depends on the
    items already chosen, so a row's first k items are exactly the
    k-slate it would have been served alone."""
    slate = _slate_impl(params, caches, pos, first, cfg=cfg,
                        slate_len=slate_len)
    with jax.named_scope("mask"):
        keep = (jnp.arange(slate_len, dtype=jnp.int32)[None, :]
                < row_lens[:, None])
        return jnp.where(keep, slate, -1)
