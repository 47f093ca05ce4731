"""Whole-model jitted inference step: the serving twin of ``TrainStep``.

Where ``TrainStep`` compiles forward+backward+optimizer into one donated
XLA program, ``InferStep`` compiles the *serving* hot paths:

- ``__call__`` — one jitted predict-mode forward (dropout off, aux state
  frozen) for scoring / encoder workloads (e.g. BERT prefill);
- ``prefill`` + ``decode_n`` — KV-cached autoregressive generation for
  nets speaking the incremental protocol (``net.prefill`` /
  ``net.decode_step``, see ``gluon.model_zoo.transformer``): prefill
  encodes the (bucket-padded) prompt and seeds per-layer
  ``(max_len, B, H, D)`` caches; ``decode_n`` runs a ``lax.while_loop``
  of O(1) incremental steps with the cache DONATED into the loop and an
  early exit once every row has emitted EOS. One jitted dispatch emits up
  to ``max_new_tokens`` tokens — no per-token host round trips
  (``tools/mxlint.py``'s ``no-sync`` pass lints ``__call__``/
  ``_dispatch``/``decode_n``).

Shape stability reuses the PR-3 machinery: prompts pad to a
``FixedBucketSampler.signatures()``-style bucket menu, ``warmup()``
drives the REAL jitted prefill+decode programs per bucket signature, the
``RecompileGuard`` counts every signature as exactly one compile and
alarms on post-warmup churn, and the persistent compilation cache makes
the programs outlive the process. ``amp='bfloat16'`` casts float params
(minus the ``amp.lists`` norm families) ONCE at build — inference has no
master-weight round trip, so the cast is free after construction.

Env knobs: ``MXTPU_DECODE_MAX_LEN`` (default decode cache capacity, 256).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .. import compile_cache as _cc
from .. import telemetry as _tel
from . import sharding as _sharding

__all__ = ["InferStep", "decode_max_len"]


def decode_max_len(default: int = 256) -> int:
    """``MXTPU_DECODE_MAX_LEN``: default KV-cache capacity (= prompt-side
    decode slots) for engines built without an explicit ``max_len``."""
    v = os.environ.get("MXTPU_DECODE_MAX_LEN", "").strip()
    try:
        return int(v) if v else default
    except ValueError:
        return default


def _sample_tokens(logits, key, method, top_k, temperature):
    """Next-token draw from (B, V) logits. ``method``/``top_k`` are
    trace-time constants; ``temperature`` is a traced scalar so serving
    can change it without recompiling."""
    if method == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if method == "top_k":
        vals, idx = jax.lax.top_k(logits, top_k)
        draw = jax.random.categorical(key, vals / temperature, axis=-1)
        return jnp.take_along_axis(idx, draw[:, None], axis=1)[:, 0].astype(
            jnp.int32)
    if method == "sample":
        return jax.random.categorical(
            key, logits / temperature, axis=-1).astype(jnp.int32)
    raise MXNetError(f"unknown sampling method {method!r}; "
                     "use greedy/top_k/sample")


def _take_counts(tokens, state):
    """Device-side counts ride the read-back the scheduler already makes:
    where a net keeps ``state["counts"]`` (int32, added to by its layers),
    they are appended to the tokens a paged program hands back, as extra
    columns of a ``(rows, n)`` block or at the end of a ``(rows,)``
    vector, and zeroed in the state. A state without them (the
    encoder-decoder's) passes through untouched, so its programs keep
    their form."""
    counts = state.get("counts")
    if counts is None:
        return tokens, state
    if tokens.ndim == 2:
        rows = tokens.shape[0]
        width = -(-counts.shape[0] // rows)
        pad = jnp.zeros((rows * width - counts.shape[0],), counts.dtype)
        tokens = jnp.concatenate(
            [tokens, jnp.concatenate([counts, pad]).reshape(rows, width)], 1)
    else:
        tokens = jnp.concatenate([tokens, counts])
    return tokens, dict(state, counts=jnp.zeros_like(counts))


class InferStep:
    """Compile a net's inference paths into jitted, shape-stable programs.

    Parameters
    ----------
    net : initialized Gluon Block. Any net gets the jitted ``__call__``
        forward; nets implementing the incremental protocol
        (``prefill(src, tgt_prefix, src_valid_length, max_len)`` +
        ``decode_step(tokens, pos, state)``) additionally get
        ``prefill``/``decode_n``/``generate``.
    mesh / data_spec : optional GSPMD placement for batch inputs; with
        no explicit mesh the process-global one
        (``sharding.global_mesh()`` / ``MXTPU_MESH``) is adopted.
    sharding : ``sharding.ShardingRules``, preset string or None (the
        ``MXTPU_SHARDING`` default). Parameters are placed under the
        rules — ``'fsdp'`` serves a model whose full params exceed one
        chip's HBM (GSPMD gathers shards per layer); default/None keeps
        the replicated-params + sharded-batch serving layout.
    amp : 'bfloat16'/'float16' — cast float params (minus ``amp.lists``
        norm families) once at build; activations follow the param dtype.
    max_len : decode cache capacity (``MXTPU_DECODE_MAX_LEN`` default).
    bos_id / eos_id / pad_id : special token ids for generation.
    """

    def __init__(self, net, mesh: Optional[Mesh] = None,
                 data_spec=None, amp: Optional[str] = None,
                 max_len: Optional[int] = None,
                 bos_id: int = 1, eos_id: int = 2, pad_id: int = 0,
                 sharding=None):
        from .. import amp as _amp_mod

        self._net = net
        rules = _sharding.ShardingRules.resolve(sharding)
        if mesh is None:
            mesh = _sharding.global_mesh()
        self._sharding_rules = rules
        self._mesh = mesh
        self._max_len = int(max_len) if max_len is not None \
            else decode_max_len()
        self._bos, self._eos, self._pad = int(bos_id), int(eos_id), int(pad_id)
        if amp is not None:
            amp = str(amp)
            if amp not in ("bfloat16", "float16"):
                raise MXNetError("amp must be 'bfloat16' or 'float16'")
        self._amp = amp
        self._params = list(net.collect_params().items())
        for name, p in self._params:
            if p._data is None:
                raise MXNetError(
                    f"parameter {name} not initialized; run one forward (or "
                    "initialize with known shapes) before building InferStep")
        fp32_pinned = _amp_mod.fp32_param_names(net) if amp else frozenset()
        cdt = jnp.dtype(amp) if amp else None

        def _cast(name, v):
            # inference AMP: no fp32 masters needed — cast ONCE at build,
            # norm-family params pinned fp32 per amp.lists
            if cdt is not None and name not in fp32_pinned and \
                    jnp.issubdtype(v.dtype, jnp.floating):
                return v.astype(cdt)
            return v

        # param placement: the rules' spec per param (FSDP-sharded
        # serving), else replicated — serving's classic layout
        if mesh is not None:
            if rules is not None:
                def _param_sharding(name, shape):
                    return rules.param_sharding(mesh, name, shape)
            else:
                def _param_sharding(name, shape):
                    return NamedSharding(mesh, PartitionSpec())
        else:
            _param_sharding = None
        self._param_sharding = _param_sharding
        vals = {}
        for name, p in self._params:
            v = _cast(name, p._data.data)
            if _param_sharding is not None:
                v = jax.device_put(v, _param_sharding(name, v.shape))
            vals[name] = v
        # the LIVE param buffer: hot-swap (swap_params) stages a full
        # replacement dict and flips this reference atomically between
        # dispatches — dispatch paths snapshot it once per dispatch so a
        # request's prefill and decode always see one coherent version
        self._values = vals
        self._version_counter = 0
        self._weights_version = "v0"
        self._cache_dtype = cdt
        if mesh is not None:
            _sharding.publish_shard_metrics(vals, mesh, rules)

        # batch placement (mirrors TrainStep's data_spec contract)
        if mesh is not None:
            if data_spec is None:
                data_spec = PartitionSpec("data") \
                    if "data" in mesh.axis_names else PartitionSpec()
            if isinstance(data_spec, (tuple, list)) and not isinstance(
                    data_spec, PartitionSpec):
                self._data_sharding = [NamedSharding(mesh, s)
                                       for s in data_spec]
            else:
                self._data_sharding = NamedSharding(mesh, data_spec)
        else:
            self._data_sharding = None

        # speculative decoding: attach_draft() fills these — the draft
        # engine plus the (target, draft, version) coherent-pair snapshot
        self.draft: Optional["InferStep"] = None
        self._live_pair = None
        self._fwd_tree = [None]  # output treedef captured at trace time
        self._fwd_fn = self._build_forward()
        # predict mode draws no randomness: one fixed key serves every
        # forward dispatch (built here so _dispatch stays pure dispatch)
        self._fixed_key = jax.random.PRNGKey(0)
        self._prefill_fns = {}  # max_len is closed over; keyed by it
        self._decode_fns = {}   # (max_new, method, top_k) -> jitted fn
        self._paged_fns = {}    # paged prefill/decode-iter programs
        self.compile_guard = _cc.RecompileGuard(
            f"InferStep({type(net).__name__})")
        _tel.set_info(amp_dtype=self._amp, infer_engine=type(net).__name__)

    @property
    def supports_decode(self) -> bool:
        return hasattr(self._net, "prefill") and \
            hasattr(self._net, "decode_step")

    @property
    def supports_paged(self) -> bool:
        """Whether the net speaks the PAGED protocol (``prefill_paged`` /
        ``decode_step_paged`` / ``init_paged_state``) — the continuous-
        batching engine path (``serving.ContinuousBatcher``)."""
        return hasattr(self._net, "decode_step_paged") and \
            hasattr(self._net, "init_paged_state") and \
            (hasattr(self._net, "prefill_paged")
             or not self.slot_state["encoder_memory"])

    @property
    def slot_state(self) -> dict:
        """What a serving slot keeps for this net, as the net declares it
        (``paged_slot_state``), the three kinds of slot state in one
        place:

        - ``pools``: the names of the PAGED arrays (a tuple of arrays
          under each name, one for each layer that keeps them; a net may
          keep them for some of its layers only), all under the one page
          table: they grow with the context, a page at a time. What a
          page holds is the net's: K and V by head (the default), K, V
          and an indexer's keys, or ONE latent vector a token with no
          head axis (``latent_pools``). A pool is ``(num_pages, page,
          ...)``, or ``(num_pages, page x heads, D)`` where few wide heads
          would be padded on an axis of their own (two heads of 128: the
          chip tiles the last two axes by ``(16, 128)``; nothing here
          reads a pool's shape past its first axis but the net), or ``(planes, num_pages, page, ...)`` for a net that
          runs one stack of weights several times a token and keeps a
          K/V PLANE for every pass (``model_zoo/ouro.py``): a page id
          then names that page in every plane, page 0 is every plane's
          trash page, and the provisioned bytes count the plane axis.
        - ``encoder_memory``: whether the slot also holds static ENCODER
          memory (per-slot ``cross_k`` / ``cross_v`` buffers and
          ``mem_vl``), of the largest bucket's width.
        - ``slot_arrays``: the names of arrays indexed by SLOT (again a
          tuple of arrays under each name, ``(slots, ...)``), of a fixed
          size whatever the context: a state-space layer's recurrent
          state and its convolution's tail. The net's chunk program
          carries them from chunk to chunk of a prompt and starts from
          zero where ``q_offset`` is 0, so a re-admitted slot needs no
          reset; its decode step leaves the arrays of rows that are not
          ``active`` as they are. A net may keep pools AND slot arrays in
          the SAME layer (``model_zoo/zaya.py``: K/V pages beside the
          tail of a convolution over the last positions and a shifted
          value, in every layer): what a page then holds is a function of
          the slot's tail too, so the chunk program has to carry the tail
          over chunk boundaries at any offset and leave the one of the
          row's last REAL token.

        ``step_tokens`` is the most tokens a decode step yields a row: 1,
        or 2 for a net that drafts the token after next itself and
        verifies it in the next step. Its ``decode_step_paged`` hands back
        ``(logits (B, 2, vocab), draft (B,))``; ``decode_iter`` then
        returns four columns a step, ``[token, second token, count,
        draft]``, the COUNT A ROW beside the tokens it counts, and the
        scheduler moves each row by its count.

        ``counts`` names the device-side counts a net adds to
        ``state["counts"]``, ``(name, length)`` in order
        (``_take_counts``); ``mtp_drafts`` and ``mtp_accepted``, where a
        net names them, are added by the decode burst itself. A net that
        declares nothing is an encoder-decoder: K and V pools, encoder
        memory, no slot arrays, no counts, one token a step. The batcher
        builds cross buffers, valid lengths and
        the cross-frame store only where ``encoder_memory`` is true;
        without it the prompt lives in the pages and enters in
        chunks."""
        return {"pools": ("k_pools", "v_pools"), "encoder_memory": True,
                "slot_arrays": (), "counts": (), "step_tokens": 1,
                **(getattr(self._net, "paged_slot_state", None) or {})}

    def _need_encoder_memory(self, what: str):
        if not self.slot_state["encoder_memory"]:
            raise MXNetError(
                f"{what} is not built for {type(self._net).__name__}: its "
                "slots keep no encoder memory (paged_slot_state), and "
                f"{what} is written against per-slot cross buffers")

    def _state_sig(self, state):
        """The part of a paged state that names a compiled program: the
        shape of the first paged array the net declares (of the first
        layer that keeps one; its plane axis too, where the pool has one:
        as many passes are as much a part of the program as the page
        size), the first cross buffer's where it keeps
        encoder memory, and the shape of the first array under each name
        of its slot arrays (their leading axis is the slot count, which
        the pools do not show)."""
        decl = self.slot_state
        return (state[decl["pools"][0]][0].shape,
                state["cross_k"][0].shape if decl["encoder_memory"]
                else None,
                *(state[name][0].shape for name in decl["slot_arrays"]))

    @property
    def weights_version(self) -> str:
        """Tag of the param set serving new dispatches. Responses carry
        the version of their final iteration (``serving.ContinuousBatcher``
        stamps it onto each ``GenerationResult``)."""
        return self._weights_version

    # ---------------------------------------------------------------- build
    def _net_scope(self, values, key):
        """Context stack for tracing the net functionally: params resolve
        to the (cast, device) values, predict mode, supplied PRNG key."""
        import contextlib

        from ..gluon.block import _aux_scope, _trace_scope
        from ..gluon.parameter import param_override
        from .. import autograd
        from .. import random as _random
        from . import mesh_scope as _mesh_scope

        name2p = {n: p for n, p in self._params}
        mapping = {name2p[n]: NDArray(v) for n, v in values.items()}
        stack = contextlib.ExitStack()
        if self._mesh is not None:
            stack.enter_context(_mesh_scope(self._mesh))
        stack.enter_context(param_override(mapping))
        stack.enter_context(_random.key_supply(key))
        stack.enter_context(_aux_scope({}))  # aux writes dropped: predict
        stack.enter_context(_trace_scope())
        stack.enter_context(autograd._scope(False, False))
        return stack

    def _build_forward(self):
        net, tree_holder = self._net, self._fwd_tree

        def fwd(values, batch, key):
            with self._net_scope(values, key):
                out = net(*[NDArray(b) for b in batch])
            leaves, tree = jax.tree.flatten(
                out, is_leaf=lambda x: isinstance(x, NDArray))
            tree_holder[0] = tree
            return tuple(o.data if isinstance(o, NDArray) else jnp.asarray(o)
                         for o in leaves)

        return jax.jit(fwd)

    def _get_prefill_fn(self, max_len):
        fn = self._prefill_fns.get(max_len)
        if fn is not None:
            return fn
        net, cache_dtype = self._net, self._cache_dtype

        def prefill(values, src, vl, prime, seed, temperature):
            # the second half of the seed's key, as decode_n always split it
            _, key = jax.random.split(jax.random.PRNGKey(seed))
            with self._net_scope(values, key):
                logits, state = net.prefill(
                    NDArray(src), NDArray(prime),
                    src_valid_length=NDArray(vl), max_len=max_len,
                    cache_dtype=cache_dtype)
            return logits.data.astype(jnp.float32), state

        fn = jax.jit(prefill)
        self._prefill_fns[max_len] = fn
        return fn

    def _get_decode_fn(self, max_new, method, top_k):
        cfg = (max_new, method, top_k)
        fn = self._decode_fns.get(cfg)
        if fn is not None:
            return fn
        net, eos, pad = self._net, self._eos, self._pad

        def decode(values, state, first_logits, prefix_len, seed,
                   temperature):
            B = first_logits.shape[0]
            key, _ = jax.random.split(jax.random.PRNGKey(seed))
            key, sub = jax.random.split(key)
            tok0 = _sample_tokens(first_logits, sub, method, top_k,
                                  temperature)
            buf = jnp.full((B, max_new), pad, jnp.int32)
            buf = jax.lax.dynamic_update_slice(buf, tok0[:, None], (0, 0))
            fin0 = tok0 == eos

            def cond(c):
                i = c[0]
                return jnp.logical_and(i < max_new,
                                       jnp.logical_not(jnp.all(c[2])))

            def body(c):
                i, tok, fin, st, k, bf = c
                # tok is the PREVIOUS emitted token buf[i-1]: it sits at
                # absolute target position prefix_len + i - 1
                with self._net_scope(values, jax.random.PRNGKey(0)):
                    logits, st = net.decode_step(
                        tok, prefix_len + i - 1, st)
                logits = logits.data if isinstance(logits, NDArray) \
                    else logits
                k, sk = jax.random.split(k)
                nxt = _sample_tokens(logits.astype(jnp.float32), sk, method,
                                     top_k, temperature)
                nxt = jnp.where(fin, jnp.int32(pad), nxt)
                bf = jax.lax.dynamic_update_slice(bf, nxt[:, None], (0, i))
                fin = jnp.logical_or(fin, nxt == eos)
                return i + 1, nxt, fin, st, k, bf

            _, _, fin, _, _, buf = jax.lax.while_loop(
                cond, body, (jnp.int32(1), tok0, fin0, state, key, buf))
            has_eos = (buf == eos).any(axis=1)
            first_eos = jnp.argmax(buf == eos, axis=1)
            lengths = jnp.where(has_eos, first_eos + 1,
                                jnp.int32(max_new)).astype(jnp.int32)
            return buf, lengths

        # the cache pytree (argument 1) is DONATED into the loop, on every
        # backend alike. It is not an output, so a single-device program
        # has nothing to alias it with and jax says so once per compile
        # ("Some donated buffers were not usable"); a program partitioned
        # over a mesh hands XLA the buffers to reuse.
        fn = jax.jit(decode, donate_argnums=(1,))
        self._decode_fns[cfg] = fn
        return fn

    # ----------------------------------------------------------------- call
    def __call__(self, *batch):
        """One jitted predict-mode forward. Accepts NDArrays / arrays;
        returns the net's outputs as NDArrays. Pure dispatch after
        ``_stage`` — the lint keeps it sync-free."""
        from ..imperative import flush_bulk

        flush_bulk()
        staged = self._stage(batch)
        return self._dispatch(staged)

    def _stage(self, batch):
        """Host-side staging (slow path): convert + optional device_put."""
        arrs = [b.data if isinstance(b, NDArray) else jnp.asarray(b)
                for b in batch]
        sh = self._data_sharding
        if sh is not None:
            per = sh if isinstance(sh, list) else [sh] * len(arrs)
            if len(per) != len(arrs):
                raise MXNetError(
                    f"data_spec sequence has {len(per)} specs but the "
                    f"forward takes {len(arrs)} inputs")
            arrs = [jax.device_put(a, s) for a, s in zip(arrs, per)]
        return tuple(arrs)

    def _dispatch(self, staged):
        """Hot dispatch: signature accounting + the jitted call. Must stay
        free of host syncs (``tools/mxlint.py``, pass ``no-sync``)."""
        sig = ("fwd",) + tuple((a.shape, a.dtype.name) for a in staged)
        self.compile_guard.observe(
            sig, lambda: "fwd " + _cc.aval_summary(staged))
        vals = self._values  # one coherent read per dispatch (hot swap)
        outs = self._fwd_fn(vals, staged, self._fixed_key)
        nds = [NDArray(o) for o in outs]
        out = jax.tree.unflatten(self._fwd_tree[0], nds)
        return out

    # --------------------------------------------------------------- decode
    @staticmethod
    def _decode_cfg(max_new_tokens, method, top_k, seed):
        """Host-side config normalization (kept out of the linted decode
        dispatch — these are Python-value coercions, never device reads).
        The seed comes back as the int32 operand of ``_seed_operand``."""
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        return max_new, str(method), int(top_k), \
            InferStep._seed_operand(seed)

    def _stage_src(self, src, src_valid_length):
        src = src.data if isinstance(src, NDArray) else jnp.asarray(src)
        src = src.astype(jnp.int32)
        if src_valid_length is None:
            vl = jnp.full((src.shape[0],), src.shape[1], jnp.int32)
        else:
            vl = src_valid_length.data \
                if isinstance(src_valid_length, NDArray) \
                else jnp.asarray(src_valid_length)
            vl = vl.astype(jnp.int32)
        if self._data_sharding is not None and not isinstance(
                self._data_sharding, list):
            src = jax.device_put(src, self._data_sharding)
        return src, vl

    def decode_n(self, src, src_valid_length=None, max_new_tokens=32,
                 method="greedy", top_k=0, temperature=1.0, seed=None,
                 prefix=None):
        """KV-cached generation: ONE prefill dispatch + ONE decode-loop
        dispatch; returns ``(tokens (B, max_new), lengths (B,))`` as
        NDArrays, asynchronously (no host sync — the decode hot path is
        linted). ``prefix`` overrides the BOS priming column with an
        explicit (B, Lp) target prefix. ``seed`` goes to both programs
        as an integer operand; each takes its half of the split key
        inside (``_seed_operand`` says how a seed past int32 folds)."""
        if not self.supports_decode:
            raise MXNetError(
                f"{type(self._net).__name__} does not implement the "
                "incremental protocol (prefill/decode_step)")
        max_new, method, top_k, seed = self._decode_cfg(
            max_new_tokens, method, top_k, seed)
        src, vl = self._stage_src(src, src_valid_length)
        B = src.shape[0]
        if prefix is None:
            prime = jnp.full((B, 1), self._bos, jnp.int32)
        else:
            prime = (prefix.data if isinstance(prefix, NDArray)
                     else jnp.asarray(prefix)).astype(jnp.int32)
        if prime.shape[1] + max_new > self._max_len:
            raise MXNetError(
                f"prefix {prime.shape[1]} + max_new_tokens {max_new} "
                f"exceeds the decode cache capacity max_len={self._max_len} "
                "(MXTPU_DECODE_MAX_LEN / InferStep(max_len=...))")
        temp = np.float32(temperature)
        cfg = (max_new, method, top_k)
        sig = ("decode", cfg, (src.shape, src.dtype.name),
               (prime.shape, prime.dtype.name))
        self.compile_guard.observe(
            sig, lambda: f"decode{cfg} " + _cc.aval_summary((src, prime)))
        prefill_fn = self._get_prefill_fn(self._max_len)
        decode_fn = self._get_decode_fn(*cfg)
        # snapshot the live buffer ONCE: a concurrent hot swap flips
        # self._values between dispatches, and this request's prefill and
        # decode must run on the same weights
        vals = self._values
        logits, state = prefill_fn(vals, src, vl, prime, seed, temp)
        toks, lengths = decode_fn(vals, state, logits,
                                  np.int32(prime.shape[1]), seed, temp)
        return NDArray(toks), NDArray(lengths)

    # ---------------------------------------------------------- paged decode
    # Continuous batching (ISSUE 8): decode runs as ONE dispatch per
    # ITERATION over a shared paged KV pool instead of one while_loop per
    # request batch. Between iterations the scheduler (serving.
    # ContinuousBatcher) retires EOS rows, frees their pages and admits
    # queued requests into the vacated slots — the dispatch shapes (slot
    # count, page-table width, pool size) never change, so the whole
    # serving loop compiles exactly twice per bucket menu entry (one
    # admission prefill + one decode-iteration program) and never again.

    def init_paged_state(self, slots, num_pages, page_size, mem_len):
        """Allocate the device-side paged decode state (per-layer pools +
        per-slot cross-attention buffers) in the engine's cache dtype.
        ``num_pages`` counts ALLOCATABLE pages; one extra trash page (id
        0) is added, matching ``serving.pages.PagePool`` ids."""
        if not self.supports_paged:
            raise MXNetError(
                f"{type(self._net).__name__} does not implement the paged "
                "protocol (prefill_paged/decode_step_paged)")
        return self._net.init_paged_state(
            int(slots), int(num_pages) + 1, int(page_size), int(mem_len),
            dtype=self._cache_dtype)

    def _get_paged_prefill_fn(self, method, top_k):
        cfg = ("paged_prefill", method, top_k)
        fn = self._paged_fns.get(cfg)
        if fn is not None:
            return fn
        net, bos = self._net, self._bos

        def prefill(values, state, src, vl, slot_ids, first_pages, active,
                    seed, temperature):
            B = src.shape[0]
            prime = jnp.full((B, 1), bos, jnp.int32)
            key = jax.random.PRNGKey(seed)
            with self._net_scope(values, key):
                logits, new_state = net.prefill_paged(
                    NDArray(src), NDArray(prime), NDArray(vl), state,
                    slot_ids, first_pages, active)
            logits = logits.data if isinstance(logits, NDArray) else logits
            key, sub = jax.random.split(key)
            tok0 = _sample_tokens(logits.astype(jnp.float32), sub, method,
                                  top_k, temperature)
            return tok0, new_state

        fn = jax.jit(prefill, donate_argnums=(1,))
        self._paged_fns[cfg] = fn
        return fn

    def _get_suffix_fn(self, method, top_k, wide=False):
        cfg = ("paged_suffix", method, top_k, bool(wide))
        fn = self._paged_fns.get(cfg)
        if fn is not None:
            return fn
        net, wide = self._net, bool(wide)

        def prefill(values, state, tokens, token_vl, q_offset,
                    page_tables, slot_ids, active, seed, temperature):
            key = jax.random.PRNGKey(seed)
            with self._net_scope(values, key):
                logits, new_state = net.prefill_suffix_paged(
                    NDArray(tokens), token_vl, q_offset, state,
                    page_tables, slot_ids, active, wide=wide)
            logits = logits.data if isinstance(logits, NDArray) else logits
            key, sub = jax.random.split(key)
            tok0 = _sample_tokens(logits.astype(jnp.float32), sub, method,
                                  top_k, temperature)
            return _take_counts(tok0, new_state)

        fn = jax.jit(prefill, donate_argnums=(1,))
        self._paged_fns[cfg] = fn
        return fn

    def _get_decode_iter_fn(self, steps, method, top_k):
        cfg = ("decode_iter", steps, method, top_k)
        fn = self._paged_fns.get(cfg)
        if fn is not None:
            return fn
        net, eos, pad = self._net, self._eos, self._pad
        if self.slot_state["step_tokens"] == 2:
            fn = jax.jit(self._decode_two(steps, method, top_k),
                         donate_argnums=(1,))
            self._paged_fns[cfg] = fn
            return fn

        def decode(values, state, page_tables, tokens, lengths, active,
                   seed, temperature):
            B = tokens.shape[0]
            key = jax.random.PRNGKey(seed)
            buf = jnp.full((B, steps), pad, jnp.int32)
            fin0 = jnp.logical_not(active)

            def body(j, c):
                tok, fin, st, k, bf = c
                live = jnp.logical_not(fin)
                with self._net_scope(values, jax.random.PRNGKey(0)):
                    logits, st = net.decode_step_paged(
                        NDArray(tok), lengths + j, st, page_tables, live)
                logits = logits.data if isinstance(logits, NDArray) \
                    else logits
                k, sk = jax.random.split(k)
                nxt = _sample_tokens(logits.astype(jnp.float32), sk,
                                     method, top_k, temperature)
                nxt = jnp.where(fin, jnp.int32(pad), nxt)
                bf = jax.lax.dynamic_update_slice(
                    bf, nxt[:, None], (0, j))
                fin = jnp.logical_or(fin, nxt == eos)
                return nxt, fin, st, k, bf

            _, _, state, _, buf = jax.lax.fori_loop(
                0, steps, body, (tokens, fin0, state, key, buf))
            return _take_counts(buf, state)

        fn = jax.jit(decode, donate_argnums=(1,))
        self._paged_fns[cfg] = fn
        return fn

    def _decode_two(self, steps, method, top_k):
        """The decode burst of a net whose step yields up to two tokens a
        row (``slot_state["step_tokens"]`` 2). A step feeds the row's last
        token and the net's own draft of the next at positions ``[p, p +
        1]``; ``g0`` and ``g1`` are the tokens at the two. Where the draft
        IS ``g0`` (greedy sampling alone: any other method keeps one token
        a step) the row yields ``g0, g1`` and moves two positions, else
        ``g0`` and one, and the next step overwrites what this one cached
        at ``p + 1``. Every token handed back is the model's own at its
        position whatever the draft. Four columns a step: ``[g0, g1 or
        pad, count, draft]``."""
        net, eos, pad = self._net, self._eos, self._pad
        names = [n for n, _ in self.slot_state["counts"]]
        at = [names.index(n) if n in names else None
              for n in ("mtp_drafts", "mtp_accepted")]

        def decode(values, state, page_tables, tokens, lengths, active,
                   seed, temperature):
            B = tokens.shape[0]
            buf = jnp.full((B, steps, 4), pad, jnp.int32)

            def body(j, c):
                tok, pos, fin, st, k, bf = c
                live = jnp.logical_not(fin)
                with self._net_scope(values, jax.random.PRNGKey(0)):
                    (logits, draft), st = net.decode_step_paged(
                        NDArray(tok), pos, st, page_tables, live)
                logits = logits.astype(jnp.float32)
                k, sk = jax.random.split(k)
                g0 = _sample_tokens(logits[:, 0], sk, method, top_k,
                                    temperature)
                g1 = jnp.argmax(logits[:, 1], axis=-1).astype(jnp.int32)
                took = jnp.logical_and(live, jnp.logical_and(
                    draft == g0, g0 != eos)) if method == "greedy" \
                    else jnp.zeros_like(live)
                count = live.astype(jnp.int32) + took
                row = jnp.stack([jnp.where(live, g0, pad),
                                 jnp.where(took, g1, pad), count, draft], 1)
                bf = jax.lax.dynamic_update_slice(bf, row[:, None],
                                                  (0, j, 0))
                fin = jnp.logical_or(fin, jnp.logical_or(
                    g0 == eos, jnp.logical_and(took, g1 == eos)))
                if None not in at and method == "greedy":
                    counts = st["counts"].at[at[0]].add(jnp.sum(live)) \
                        .at[at[1]].add(jnp.sum(took))
                    st = dict(st, counts=counts)
                return jnp.where(took, g1, g0), pos + count, fin, st, k, bf

            _, _, _, state, _, buf = jax.lax.fori_loop(
                0, steps, body,
                (tokens, lengths, jnp.logical_not(active), state,
                 jax.random.PRNGKey(seed), buf))
            return _take_counts(buf.reshape(B, steps * 4), state)

        return decode

    @staticmethod
    def _paged_cfg(method, top_k, seed, steps=1):
        """Host-side config normalization (kept out of the linted paged
        dispatches — Python-value coercions, never device reads). The
        seed comes back as the int32 operand of ``_seed_operand``."""
        return str(method), int(top_k), InferStep._seed_operand(seed), \
            max(int(steps), 1)

    @staticmethod
    def _seed_operand(seed):
        """The seed as the compiled programs take it: an ``np.int32``
        scalar, from which they make ``jax.random.PRNGKey(seed)`` inside
        (no eager key before a dispatch; a greedy program drops it). For
        every seed an int32 holds, that key equals the eager
        ``jax.random.PRNGKey(seed)`` bit for bit. A larger Python integer
        is folded on the host the way the eager form folds it with 64-bit
        mode off: its low 32 bits, read as a signed int32. One that no
        int64 holds raises ``OverflowError``, as the eager form did."""
        return np.int64(0 if seed is None else int(seed)).astype(np.int32)

    @staticmethod
    def _operands(dtype, *xs):
        """Per-dispatch operands as the ONE compiled call takes them
        (kept out of the linted dispatches: host coercions live here).
        A host operand (numpy array, list, scalar) becomes a PRIVATE
        numpy copy of ``dtype``, which the call itself uploads: no eager
        put stands before the program, and the copy matters because the
        call may read host memory after it returns (the CPU client
        aliases an aligned numpy buffer outright) while the scheduler
        rewrites ``pool.table`` and its staging arrays for the next pass.
        An operand already on the device (``jax.Array``, ``NDArray``)
        passes through untouched and is never pulled to the host; only a
        dtype that differs is cast there."""
        out = []
        for x in xs:
            if isinstance(x, NDArray):
                x = x.data
            if isinstance(x, jax.Array):
                out.append(x if x.dtype == dtype else x.astype(dtype))
            else:
                out.append(np.array(x, dtype))
        return out

    def prefill_paged(self, state, src, src_valid_length, slot_ids,
                      first_pages, active, method="greedy", top_k=0,
                      temperature=1.0, seed=0):
        """One admission dispatch: prefill the (padded) admission batch
        INTO pool pages/slot buffers and sample each admitted row's first
        token. Pure staging + dispatch, sync-free by lint
        (``tools/mxlint.py``, pass ``no-sync``) — the scheduler reads the
        returned tokens at its designated sync point. Returns
        ``(tok0 (slots,) NDArray, new_state)``.

        Like every paged entry point this is ONE enqueue: the per-
        dispatch operands may be host arrays (numpy, lists: copied on the
        host and uploaded by the compiled call itself, so the caller may
        rewrite them as soon as this returns) or device arrays
        (``jax.Array`` / ``NDArray``: handed on untouched, never read
        back). ``seed`` is an integer operand: the program makes
        ``jax.random.PRNGKey(seed)`` inside, bit for bit the eager key
        for an int32 seed; a larger one folds to its low 32 bits
        (``_seed_operand``), as the eager form folded it."""
        self._need_encoder_memory("prefill_paged (a whole-bucket prefill "
                                  "primed with BOS)")
        src, vl, slot_ids, first_pages = self._operands(
            np.int32, src, src_valid_length, slot_ids, first_pages)
        active, = self._operands(np.bool_, active)
        temp, = self._operands(np.float32, temperature)
        method, top_k, seed, _ = self._paged_cfg(method, top_k, seed)
        cfg = (method, top_k)
        sig = ("paged_prefill", cfg, (src.shape, src.dtype.name),
               *self._state_sig(state))
        self.compile_guard.observe(
            sig, lambda: f"paged_prefill{cfg} " + _cc.aval_summary((src,)))
        fn = self._get_paged_prefill_fn(*cfg)
        vals = self._values  # one coherent weight snapshot per dispatch
        tok0, new_state = fn(vals, state, src, vl, slot_ids, first_pages,
                             active, seed, temp)
        return NDArray(tok0), new_state

    def prefill_suffix_paged(self, state, tokens, token_vl, q_offset,
                             page_tables, slot_ids, active,
                             method="greedy", top_k=0, temperature=1.0,
                             seed=0, wide=False):
        """Prefix-cache admission dispatch: run the decode-side forward
        over ONLY each row's uncached suffix (absolute positions
        ``q_offset[r] + j``) and sample its first new token. The encoder
        never runs — cross memory comes from the adopted cache root (or
        a prior prefill). ``wide`` routes the replay through the ONE-pass
        q_offset-aware window program (paged flash kernel when enabled)
        instead of the bit-exact sequential stream. Same staging/guard/
        donation contract as ``prefill_paged`` (one enqueue; host or
        device operands; ``seed`` an integer operand); sync-free by lint.
        Returns ``(tok0 (B,) NDArray, new_state)``."""
        tokens, token_vl, q_offset, page_tables, slot_ids = self._operands(
            np.int32, tokens, token_vl, q_offset, page_tables, slot_ids)
        active, = self._operands(np.bool_, active)
        temp, = self._operands(np.float32, temperature)
        method, top_k, seed, _ = self._paged_cfg(method, top_k, seed)
        wide = True if wide else False
        cfg = (method, top_k, wide)
        sig = ("paged_suffix", cfg, (tokens.shape, tokens.dtype.name),
               page_tables.shape, *self._state_sig(state))
        self.compile_guard.observe(
            sig, lambda: f"paged_suffix{cfg} "
            + _cc.aval_summary((tokens,)))
        fn = self._get_suffix_fn(*cfg)
        vals = self._values  # one coherent weight snapshot per dispatch
        tok0, new_state = fn(vals, state, tokens, token_vl, q_offset,
                             page_tables, slot_ids, active, seed, temp)
        return NDArray(tok0), new_state

    def decode_iter(self, state, page_tables, tokens, lengths, active,
                    steps=1, method="greedy", top_k=0, temperature=1.0,
                    seed=0):
        """One decode ITERATION over the slot batch: ``steps`` incremental
        tokens per live row in a single jitted dispatch, K/V read and
        written through ``page_tables``. The big pool state is the
        donated carry; tokens/lengths/active and the page table are
        small per-dispatch operands, host arrays (copied, then uploaded
        by the one compiled call) or device arrays (passed through).
        ``seed`` is an integer operand, the key is made in the program
        (``prefill_paged`` says how a seed past int32 folds). Sync-free
        by lint — the scheduler's collect phase is the sync point.
        Returns ``(tok_block (slots, steps) NDArray, new_state)``; for a
        net whose step yields up to two tokens a row, four columns a step
        (``_decode_two``)."""
        page_tables, tokens, lengths = self._operands(
            np.int32, page_tables, tokens, lengths)
        active, = self._operands(np.bool_, active)
        temp, = self._operands(np.float32, temperature)
        method, top_k, seed, steps = self._paged_cfg(method, top_k, seed,
                                                     steps)
        cfg = (steps, method, top_k)
        sig = ("decode_iter", cfg, (page_tables.shape, tokens.shape),
               *self._state_sig(state))
        self.compile_guard.observe(
            sig, lambda: f"decode_iter{cfg} "
            + _cc.aval_summary((page_tables, tokens)))
        fn = self._get_decode_iter_fn(steps, method, top_k)
        vals = self._values
        buf, new_state = fn(vals, state, page_tables, tokens, lengths,
                            active, seed, temp)
        return NDArray(buf), new_state

    def _get_next_carry_fn(self, steps):
        cfg = ("next_carry", steps)
        fn = self._paged_fns.get(cfg)
        if fn is not None:
            return fn
        two = self.slot_state["step_tokens"] == 2

        def carry(block, lengths):
            if not two:
                return block[:, steps - 1], lengths + steps
            # four columns a step, [token, second token, count, draft]: a
            # row moves ``count`` positions a step and its last token is
            # the last one a count reached (``_decode_two``)
            tok, moved = block[:, 0], jnp.zeros_like(lengths)
            for j in range(steps):
                first, second, count = (block[:, 4 * j + c]
                                        for c in range(3))
                tok = jnp.where(count > 1, second,
                                jnp.where(count > 0, first, tok))
                moved = moved + count
            return tok, lengths + moved

        fn = jax.jit(carry)
        self._paged_fns[cfg] = fn
        return fn

    def next_carry(self, block, lengths, steps=1):
        """The next burst's ``tokens`` and ``lengths`` from the token block
        the last burst handed back (``decode_iter``'s, counts' columns and
        all) and the lengths that burst was given, by the arithmetic the
        scheduler's collect phase does on the host: the last column and
        ``lengths + steps``, or, for a net whose step yields up to two
        tokens, the last token a ``count`` reached and ``lengths`` plus the
        counts' sum. A program of its own beside the burst, so that a
        scheduler may dispatch the burst after this one before it has read
        this one; the block stays the caller's to read (nothing is
        donated). A row that ended inside the block gets a token and a
        length nobody may use. One enqueue, sync-free by lint. Returns
        ``(tokens, lengths)`` as device arrays."""
        block, lengths = self._operands(np.int32, block, lengths)
        steps = max(steps, 1)
        sig = ("next_carry", steps, block.shape)
        self.compile_guard.observe(
            sig, lambda: f"next_carry({steps}) "
            + _cc.aval_summary((block, lengths)))
        return self._get_next_carry_fn(steps)(block, lengths)

    # ---------------------------------------------------- speculative decode
    # Speculative decoding (ISSUE 14): a small DRAFT engine proposes k
    # greedy tokens per slot (one decode_iter dispatch of its own), then
    # ONE target dispatch scores all k+1 positions and accepts the longest
    # agreeing prefix in-graph. The acceptance rule — draft token j
    # accepted iff it equals the target argmax at position j-1 — makes the
    # emitted stream EXACTLY the target's greedy output for ANY draft
    # proposals: the draft buys speed, never changes tokens. Draft and
    # target share the one PagePool table; the draft keeps its own pools.

    @property
    def has_draft(self) -> bool:
        """Whether a draft engine is attached (``attach_draft``)."""
        return self.draft is not None

    def attach_draft(self, draft_net) -> "InferStep":
        """Attach a draft engine over ``draft_net`` (same vocab and
        special ids; typically a shallower stack). The draft shares this
        engine's ``RecompileGuard`` (one steady-state accounting domain)
        and inherits its AMP/max_len config. ``spec_pair()`` snapshots
        (target params, draft params, version) as ONE tuple, reassigned
        atomically by ``swap_params`` — a spec round can therefore never
        observe mixed draft/target versions."""
        self._need_encoder_memory("speculative decoding (attach_draft)")
        draft = InferStep(draft_net, mesh=self._mesh, amp=self._amp,
                          max_len=self._max_len, bos_id=self._bos,
                          eos_id=self._eos, pad_id=self._pad)
        draft.compile_guard = self.compile_guard
        self.draft = draft
        self._live_pair = (self._values, draft._values,
                           self._weights_version)
        return draft

    def spec_pair(self):
        """One coherent ``(target_values, draft_values, version)``
        snapshot. Spec rounds read this ONCE and thread it through both
        dispatches; the swap plane flips the whole tuple in a single
        reference assignment."""
        if self._live_pair is None:
            raise MXNetError("spec_pair() needs attach_draft() first")
        return self._live_pair

    def init_draft_state(self, slots, num_pages, page_size, mem_len):
        """Paged decode state for the DRAFT engine with the same pool
        geometry as the target's — both sides are indexed by the one
        shared ``PagePool`` page table."""
        if self.draft is None:
            raise MXNetError("init_draft_state() needs attach_draft() "
                             "first")
        return self.draft.init_paged_state(slots, num_pages, page_size,
                                           mem_len)

    def _get_spec_draft_fn(self, steps, method, top_k):
        """The draft proposal program IS the draft's ``decode_iter`` with
        ``steps = k+1``: step j scatters token x_j at ``len+j`` and
        samples x_{j+1}, so proposals are ``buf[:, :k]`` and the extra
        step writes d_k's KV at ``len+k`` — a full-acceptance round
        leaves no draft-cache hole. No new program shape: the batcher's
        warmed draft decode_iter menu covers it."""
        return self.draft._get_decode_iter_fn(steps, method, top_k)

    def spec_draft(self, dstate, page_tables, tokens, lengths, active,
                   k=4, pair=None, seed=0):
        """Draft proposal dispatch: k+1 greedy draft steps per live slot
        in ONE jitted call (the draft's donated-carry decode_iter).
        ``tokens`` are the slots' carry tokens; returns ``(buf (slots,
        k+1) NDArray, new_dstate)`` — proposals are ``buf[:, :k]``, the
        last column is the hole-closing extra step. Sync-free by lint
        and one enqueue (host or device operands, ``seed`` an integer
        operand, as ``decode_iter``); pass the whole buf to
        ``spec_verify``."""
        page_tables, tokens, lengths = self._operands(
            np.int32, page_tables, tokens, lengths)
        active, = self._operands(np.bool_, active)
        temp, = self._operands(np.float32, 1.0)
        method, top_k, seed, steps = self._paged_cfg("greedy", 0, seed,
                                                     k + 1)
        cfg = (steps, method, top_k)
        sig = ("spec_draft", cfg, (page_tables.shape, tokens.shape),
               dstate["k_pools"][0].shape)
        self.compile_guard.observe(
            sig, lambda: f"spec_draft{cfg} "
            + _cc.aval_summary((page_tables, tokens)))
        fn = self._get_spec_draft_fn(steps, method, top_k)
        vals = pair[1] if pair is not None else self.draft._values
        buf, new_dstate = fn(vals, dstate, page_tables, tokens, lengths,
                             active, seed, temp)
        return NDArray(buf), new_dstate

    @staticmethod
    def _spec_cfg(drafts_width, wide):
        """Host-side spec-verify config normalization (kept out of the
        linted dispatch — Python-value coercions, never device reads)."""
        k = int(drafts_width) - 1
        if k < 1:
            raise MXNetError("spec_verify needs a (slots, k+1) draft "
                             "buffer with k >= 1")
        return k, bool(wide)

    def _get_spec_verify_fn(self, k, wide):
        """Target verification program: score all k+1 positions (carry +
        k proposals), accept in-graph. ``wide=False`` (exact mode,
        default) runs a fori_loop of the SAME ``decode_step_paged``
        program shape as plain decoding — bit-identical logits by
        construction; ``wide=True`` scores the window in one
        ``decode_window_paged`` pass (the flash-kernel fast path, equal
        argmax up to attention-order rounding). Output packs ``(slots,
        k+2)`` int32: target argmaxes t_0..t_k then the per-row emit
        count ``n_accepted + 1``."""
        cfg = ("spec_verify", k, bool(wide))
        fn = self._paged_fns.get(cfg)
        if fn is not None:
            return fn
        net = self._net

        def verify(values, state, page_tables, drafts, tokens, lengths,
                   active):
            B = drafts.shape[0]
            # x_0 = carry, x_j = draft proposal j; the draft buffer's
            # last column (the hole-closing extra step) is unused here
            x = jnp.concatenate([tokens[:, None], drafts[:, :k]], axis=1)
            if wide:
                with self._net_scope(values, jax.random.PRNGKey(0)):
                    logits, state = net.decode_window_paged(
                        NDArray(x), lengths, state, page_tables, active)
                logits = logits.data if isinstance(logits, NDArray) \
                    else logits
                t = jnp.argmax(logits.astype(jnp.float32),
                               axis=-1).astype(jnp.int32)
            else:
                tbuf = jnp.zeros((B, k + 1), jnp.int32)

                def body(j, c):
                    st, tb = c
                    tok_j = jax.lax.dynamic_index_in_dim(
                        x, j, axis=1, keepdims=False)
                    with self._net_scope(values, jax.random.PRNGKey(0)):
                        lg, st = net.decode_step_paged(
                            NDArray(tok_j), lengths + j, st, page_tables,
                            active)
                    lg = lg.data if isinstance(lg, NDArray) else lg
                    tj = jnp.argmax(lg.astype(jnp.float32),
                                    axis=-1).astype(jnp.int32)
                    return st, jax.lax.dynamic_update_slice(
                        tb, tj[:, None], (0, j))

                state, t = jax.lax.fori_loop(0, k + 1, body, (state, tbuf))
            # longest agreeing prefix: d_j accepted iff d_j == t_{j-1}
            agree = (drafts[:, :k] == t[:, :k]).astype(jnp.int32)
            n_acc = jnp.sum(jnp.cumprod(agree, axis=1), axis=1)
            count = jnp.where(active, n_acc + 1, 0).astype(jnp.int32)
            return jnp.concatenate([t, count[:, None]], axis=1), state

        fn = jax.jit(verify, donate_argnums=(1,))
        self._paged_fns[cfg] = fn
        return fn

    def spec_verify(self, state, page_tables, drafts, tokens, lengths,
                    active, pair=None, wide=False):
        """Target verification dispatch: ONE jitted call scores the carry
        token plus k proposals and accepts the longest agreeing prefix
        in-graph. ``drafts`` is ``spec_draft``'s whole (slots, k+1)
        buffer (k inferred from its width). Returns ``(out (slots, k+2)
        NDArray, new_state)``: columns 0..k are the target greedy tokens
        t_0..t_k, column k+1 the per-row emit count — the scheduler
        emits ``t_0..t_{count-1}`` and advances length by count.
        Sync-free by lint and one enqueue: ``drafts`` stays on the device
        (it is never pulled to the host), the host operands go in as
        numpy. Greedy only (spec never engages for sampled requests), so
        it takes no seed."""
        page_tables, drafts, tokens, lengths = self._operands(
            np.int32, page_tables, drafts, tokens, lengths)
        active, = self._operands(np.bool_, active)
        k, wide = self._spec_cfg(drafts.shape[1], wide)
        cfg = (k, wide)
        sig = ("spec_verify", cfg, (page_tables.shape, drafts.shape),
               state["k_pools"][0].shape)
        self.compile_guard.observe(
            sig, lambda: f"spec_verify{cfg} "
            + _cc.aval_summary((page_tables, drafts)))
        fn = self._get_spec_verify_fn(k, wide)
        vals = pair[0] if pair is not None else self._values
        out, new_state = fn(vals, state, page_tables, drafts, tokens,
                            lengths, active)
        return NDArray(out), new_state

    def decode_spec_n(self, src, src_valid_length=None, max_new_tokens=32,
                      k=4, wide=False, seed=0, page_size=16):
        """Speculative twin of ``decode_n``: one paged prefill, then host
        rounds of draft-propose + target-verify until every row finishes.
        ``k=0`` degenerates to sequential paged decoding (one
        ``decode_iter`` step per round) — the bench ablation baseline on
        the same program set. Greedy only; the acceptance rule emits
        exactly the target's greedy stream, so output matches the
        non-speculative engine token for token. Returns ``(tokens (B,
        max_new), lengths (B,))`` NDArrays (pad-filled past EOS)."""
        import numpy as _np

        max_new, _, _, seed = self._decode_cfg(max_new_tokens, "greedy",
                                               0, seed)
        k = max(int(k), 0)
        if k and self.draft is None:
            raise MXNetError("decode_spec_n(k>0) needs attach_draft()")
        src, vl = self._stage_src(src, src_valid_length)
        B, L = int(src.shape[0]), int(src.shape[1])
        page_size = int(page_size)
        cap = 1 + max_new + k + 1  # BOS + emitted + one drafted window
        pps = -(-cap // page_size)
        table = _np.zeros((B, pps), _np.int32)
        for r in range(B):
            table[r] = 1 + r * pps + _np.arange(pps)
        pair = self.spec_pair() if self.draft is not None else None
        state = self.init_paged_state(B, B * pps, page_size, L)
        slot_ids = _np.arange(B, dtype=_np.int32)
        ones = _np.ones((B,), bool)
        tok0, state = self.prefill_paged(state, src, vl, slot_ids,
                                         table[:, 0], ones, seed=seed)
        dstate = None
        if k:
            dstate = self.init_draft_state(B, B * pps, page_size, L)
            _, dstate = self.draft.prefill_paged(
                dstate, src, vl, slot_ids, table[:, 0], ones, seed=seed)
        carry = tok0.asnumpy().astype(_np.int32)
        lengths = _np.ones((B,), _np.int32)
        emitted = [[int(carry[r])] for r in range(B)]
        done = _np.array([t[0] == self._eos for t in emitted])
        while True:
            live = _np.array([not done[r] and len(emitted[r]) < max_new
                              for r in range(B)])
            if not live.any():
                break
            if k:
                dbuf, dstate = self.spec_draft(
                    dstate, table, carry, lengths, live, k=k, pair=pair,
                    seed=seed)
                out, state = self.spec_verify(
                    state, table, dbuf, carry, lengths, live, pair=pair,
                    wide=wide)
                toks = out.asnumpy()
                for r in range(B):
                    if not live[r]:
                        continue
                    adv = 0
                    for j in range(int(toks[r, k + 1])):
                        t = int(toks[r, j])
                        emitted[r].append(t)
                        carry[r] = t
                        adv += 1
                        if t == self._eos:
                            done[r] = True
                            break
                        if len(emitted[r]) >= max_new:
                            break
                    lengths[r] += adv
            else:
                buf, state = self.decode_iter(state, table, carry,
                                              lengths, live, steps=1,
                                              seed=seed)
                toks = buf.asnumpy()
                for r in range(B):
                    if not live[r]:
                        continue
                    t = int(toks[r, 0])
                    emitted[r].append(t)
                    carry[r] = t
                    lengths[r] += 1
                    if t == self._eos:
                        done[r] = True
        out_t = _np.full((B, max_new), self._pad, _np.int32)
        out_l = _np.zeros((B,), _np.int32)
        for r in range(B):
            n = min(len(emitted[r]), max_new)
            out_t[r, :n] = emitted[r][:n]
            out_l[r] = n
        return NDArray(jnp.asarray(out_t)), NDArray(jnp.asarray(out_l))

    def generate(self, src, src_valid_length=None, max_new_tokens=32,
                 **kwargs):
        """User-facing generation. Same contract as ``decode_n``; when
        telemetry is enabled the prefill and decode dispatches are timed
        (blocking — the instrumented path trades the async dispatch for
        honest ``infer/prefill_ms`` and ``infer/decode_ms_per_token``)."""
        if not _tel._ENABLED:
            return self.decode_n(src, src_valid_length,
                                 max_new_tokens=max_new_tokens, **kwargs)
        return self._generate_timed(src, src_valid_length, max_new_tokens,
                                    **kwargs)

    def _generate_timed(self, src, src_valid_length, max_new_tokens,
                        **kwargs):
        """Telemetry-instrumented generation (cold-ish path: syncs twice
        per call to attribute prefill vs decode time)."""
        import time

        reg = _tel.registry()
        t0 = time.perf_counter()
        with _tel.span("infer.decode_n"):
            toks, lengths = self.decode_n(
                src, src_valid_length, max_new_tokens=max_new_tokens,
                **kwargs)
            jax.block_until_ready(toks.data)
        total_ms = (time.perf_counter() - t0) * 1e3
        n_tokens = int(jnp.sum(lengths.data))
        reg.histogram("infer/prefill_ms").observe(total_ms)  # upper bound
        if n_tokens:
            reg.histogram("infer/decode_ms_per_token").observe(
                total_ms / n_tokens)
            reg.gauge("infer/tokens_per_sec").set(
                n_tokens / (total_ms / 1e3))
        reg.counter("infer/tokens").inc(n_tokens)
        return toks, lengths

    # -------------------------------------------------------------- warmup
    def warmup(self, signatures, max_new_tokens=None, **decode_kwargs):
        """AOT-compile the real jitted inference programs for every prompt
        signature, so the serving loop never compiles.

        ``signatures`` entries are either ``(batch, bucket)`` pairs (the
        ``FixedBucketSampler.signatures()`` menu — int32 token prompts
        assumed) or full warmup-style per-array spec sequences for the
        generic forward. With ``max_new_tokens`` set (and a decode-capable
        net) each prompt signature drives the REAL prefill+decode
        programs on zero prompts; otherwise the plain forward. Marks the
        guard steady afterwards; returns the number of fresh programs."""
        import numpy as _host_np

        reg = _tel.registry()
        before = self.compile_guard.signatures
        for entry in signatures:
            if len(entry) == 2 and all(
                    isinstance(x, (int, _host_np.integer)) for x in entry):
                bs, bucket = int(entry[0]), int(entry[1])
                src = _host_np.zeros((bs, bucket), _host_np.int32)
                vl = _host_np.full((bs,), bucket, _host_np.int32)
                if max_new_tokens is not None and self.supports_decode:
                    out = self.decode_n(src, vl,
                                        max_new_tokens=max_new_tokens,
                                        **decode_kwargs)
                    jax.block_until_ready(out[0].data)
                else:
                    out = self(src)
                    leaf = jax.tree.leaves(
                        out, is_leaf=lambda x: isinstance(x, NDArray))[0]
                    jax.block_until_ready(leaf.data)
            else:
                specs = [_cc.normalize_spec(s) for s in entry]
                host = [_host_np.zeros(shape, dtype)
                        for shape, dtype in specs]
                out = self(*host)
                leaf = jax.tree.leaves(
                    out, is_leaf=lambda x: isinstance(x, NDArray))[0]
                jax.block_until_ready(leaf.data)
        compiled = self.compile_guard.signatures - before
        reg.counter("compile/warmup_compiles").inc(compiled)
        self.compile_guard.mark_steady()
        return compiled

    def cache_info(self) -> dict:
        """Signature cache summary (``compile_cache.RecompileGuard``)."""
        return self.compile_guard.info()

    # -------------------------------------------------- weight lifecycle
    def _bump_version(self, version: Optional[str]) -> str:
        self._version_counter += 1
        self._weights_version = version if version is not None \
            else f"v{self._version_counter}"
        _tel.set_info(weights_version=self._weights_version)
        return self._weights_version

    def sync_params(self, version: Optional[str] = None):
        """Re-read the net's current parameter values (after external
        updates, e.g. ``TrainStep.sync_params`` handed fresh weights),
        re-placing each under its declared sharding and bumping
        ``weights_version``."""
        self.swap_params(
            staged=self.stage_params(
                {name: p._data.data for name, p in self._params}),
            version=version)

    def stage_params(self, arrays) -> dict:
        """Stage a full replacement param set into a standby device
        buffer; the LIVE set is untouched (double buffering — staging can
        run on a background thread while serving continues).

        ``arrays`` maps param name -> array; ``TrainStep`` checkpoint
        naming (``values/<name>``) is accepted, extra entries (optimizer
        moments, scaler state) are ignored. Every engine param must be
        present with its exact shape; values are cast to the LIVE entry's
        dtype and placed under its sharding, so flipping to the staged
        set can never change a dispatch signature (zero recompiles by
        construction)."""
        self._need_encoder_memory("hot weight swap (stage_params)")
        live = self._values
        vals = {}
        for name, _ in self._params:
            v = arrays.get(name)
            if v is None:
                v = arrays.get("values/" + name)
            if v is None:
                raise MXNetError(
                    f"swap source is missing parameter {name!r}")
            v = jnp.asarray(v)
            cur = live[name]
            if tuple(v.shape) != tuple(cur.shape):
                raise MXNetError(
                    f"swap shape mismatch for {name!r}: "
                    f"{tuple(v.shape)} != {tuple(cur.shape)}")
            v = v.astype(cur.dtype)
            if self._param_sharding is not None:
                v = jax.device_put(v, self._param_sharding(name, v.shape))
            vals[name] = v
        if self.draft is not None:
            # draft params ride the same checkpoint under a "draft/"
            # prefix; staging both here lets swap_params flip the pair
            # in one barrier step
            sub = {}
            for key, val in arrays.items():
                if key.startswith("draft/"):
                    sub[key[len("draft/"):]] = val
                elif key.startswith("values/draft/"):
                    sub["values/" + key[len("values/draft/"):]] = val
            if sub:
                vals["__draft_staged__"] = self.draft.stage_params(sub)
        return vals

    def swap_params(self, arrays=None, *, staged: Optional[dict] = None,
                    version: Optional[str] = None) -> str:
        """Hot weight swap: flip the live param buffer to ``staged`` (or
        to ``stage_params(arrays)``), atomically between dispatches.

        In-flight dispatches hold their own snapshot and finish on the
        OLD version; every dispatch entered after this call serves the
        new one. The flip itself is one reference assignment — it stalls
        serving by zero dispatches. Returns the new ``weights_version``
        (``version`` or an auto-bumped ``v<N>`` tag)."""
        if staged is None:
            if arrays is None:
                raise MXNetError("swap_params needs arrays= or staged=")
            staged = self.stage_params(arrays)
        dstaged = staged.pop("__draft_staged__", None)
        if set(staged) != {n for n, _ in self._params}:
            raise MXNetError(
                "staged param set does not cover the engine's params "
                "(use stage_params())")
        self._values = staged
        ver = self._bump_version(version)
        if self.draft is not None:
            if dstaged is not None:
                self.draft._values = dstaged
                self.draft._weights_version = ver
            # flip the PAIR last and as one tuple: spec rounds snapshot
            # it once (spec_pair), so a concurrent round sees either the
            # old (target, draft) pair or the new one — never a mix
            self._live_pair = (self._values, self.draft._values, ver)
        return ver
