"""jaxpr walker — the jitted-program IR driver.

Lowers the REAL programs the repo serves with (not toy stand-ins): a
tiny-but-complete transformer ``TrainStep`` (AMP + remat, via one real
dispatch — the same warmup signature machinery production uses) and an
``InferStep`` over the same model (dense prefill/decode plus the paged
continuous-batching programs). Passes share one ``ProgramIndex`` through
``Context.programs`` so the expensive traces happen once per lint run.

Also owns the generic jaxpr plumbing every jaxpr pass uses:
``iter_jaxprs`` (recursing into pjit/scan/cond/remat sub-jaxprs),
``iter_eqns`` and ``primitive_names``.
"""

from __future__ import annotations

from typing import Iterator, Set

_LOW = ("bfloat16", "float16")


# ------------------------------------------------------------ jaxpr walking
def iter_jaxprs(obj) -> Iterator:
    """Yield every (sub-)jaxpr reachable from a jaxpr / ClosedJaxpr /
    eqn-params value (pjit, scan, cond, while, remat, custom_vjp...)."""
    if obj is None:
        return
    if hasattr(obj, "jaxpr"):  # ClosedJaxpr
        yield from iter_jaxprs(obj.jaxpr)
        return
    if hasattr(obj, "eqns"):  # Jaxpr
        yield obj
        for eqn in obj.eqns:
            for v in eqn.params.values():
                yield from iter_jaxprs(v)
        return
    if isinstance(obj, (tuple, list)):
        for item in obj:
            yield from iter_jaxprs(item)


def iter_eqns(closed_jaxpr) -> Iterator:
    for jaxpr in iter_jaxprs(closed_jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn


def primitive_names(closed_jaxpr) -> Set[str]:
    return {eqn.primitive.name for eqn in iter_eqns(closed_jaxpr)}


def find_mixed_dots(closed_jaxpr):
    """[(primitive, operand dtypes)] for every dot_general mixing fp32
    with a low-precision operand anywhere in the program — the AMP
    purity rule (an un-cast master weight reached an MXU op)."""
    out = []
    for eqn in iter_eqns(closed_jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        dts = [str(v.aval.dtype) for v in eqn.invars[:2]
               if hasattr(v.aval, "dtype")]
        if "float32" in dts and any(d in _LOW for d in dts):
            out.append((eqn.primitive.name, tuple(dts)))
    return out


def count_low_precision_dots(closed_jaxpr) -> int:
    n = 0
    for eqn in iter_eqns(closed_jaxpr):
        if eqn.primitive.name == "dot_general" and any(
                str(v.aval.dtype) in _LOW for v in eqn.invars[:2]
                if hasattr(v.aval, "dtype")):
            n += 1
    return n


# ------------------------------------------------------- program builders
def build_train_step(amp="bfloat16", remat="dots_saveable"):
    """A minimal transformer TrainStep exercising the full hot-path
    surface (cast params, fp32-pinned norms, attention + tied-embedding
    dots, donated state), dispatched once so ``_last_avals`` holds the
    real warmup signature."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, optimizer as opt  # noqa: F401
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import TrainStep

    net = TransformerModel(src_vocab=64, tgt_vocab=64, units=16,
                           hidden_size=32, num_layers=1, num_heads=2,
                           max_length=32, dropout=0.0)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))

    class CE:
        def __call__(self, logits, label):
            x = logits.data.astype(jnp.float32)
            logp = jax.nn.log_softmax(x, axis=-1)
            nll = -jnp.take_along_axis(
                logp, label.data.astype(jnp.int32)[..., None], axis=-1)
            return NDArray(nll.mean())

    step = TrainStep(net, CE(), opt.AdamW(learning_rate=1e-4), amp=amp,
                     remat=remat)
    rng = np.random.RandomState(0)
    src = nd.array(rng.randint(0, 64, (2, 8)), dtype="int32")
    tgt = nd.array(rng.randint(0, 64, (2, 8)), dtype="int32")
    lab = nd.array(rng.randint(0, 64, (2, 8)), dtype="int32")
    step(src, tgt, lab)  # populates _last_avals
    return step


def build_infer_engine(max_len=32):
    """A decode- AND paged-capable InferStep over the tiny transformer,
    meshless (the collective-placement pass asserts the default serving
    layout dispatches no collectives in decode)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
    from mxnet_tpu.parallel.infer import InferStep

    net = TransformerModel(src_vocab=64, tgt_vocab=64, units=16,
                           hidden_size=32, num_layers=1, num_heads=2,
                           max_length=32, dropout=0.0)
    net.initialize(mx.initializer.Xavier())
    net._probe_shapes(nd.zeros((2, 8), dtype="int32"),
                      nd.zeros((2, 8), dtype="int32"))
    return InferStep(net, mesh=None, max_len=max_len)


class ProgramIndex:
    """Lazily built, cached real programs for the jaxpr passes."""

    def __init__(self):
        self._train_step = None
        self._train_jaxpr = None
        self._engine = None
        self._decode = None
        self._paged = None

    @property
    def train_step(self):
        if self._train_step is None:
            self._train_step = build_train_step()
        return self._train_step

    @property
    def train_jaxpr(self):
        if self._train_jaxpr is None:
            import jax
            step = self.train_step
            self._train_jaxpr = jax.make_jaxpr(step._step_fn)(
                *step._last_avals)
        return self._train_jaxpr

    @property
    def infer_engine(self):
        if self._engine is None:
            self._engine = build_infer_engine()
        return self._engine

    def decode_programs(self, max_new=4):
        """(prefill_jaxpr, decode_jaxpr, example-arg tuples) for the
        dense greedy decode path, traced from the engine's real cached
        jitted fns over real prefill state."""
        if self._decode is not None:
            return self._decode
        import jax
        import numpy as np

        eng = self.infer_engine
        src = np.zeros((2, 8), np.int32)
        vl = np.full((2,), 8, np.int32)
        prime = np.full((2, 1), eng._bos, np.int32)
        seed = np.int32(0)  # the programs make their key from it
        temp = np.float32(1.0)
        prefill_fn = eng._get_prefill_fn(eng._max_len)
        prefill_args = (eng._values, src, vl, prime, seed, temp)
        prefill_jaxpr = jax.make_jaxpr(prefill_fn)(*prefill_args)
        logits, state = prefill_fn(*prefill_args)
        decode_fn = eng._get_decode_fn(max_new, "greedy", 0)
        decode_args = (eng._values, state, logits, np.int32(1), seed, temp)
        decode_jaxpr = jax.make_jaxpr(decode_fn)(*decode_args)
        self._decode = (prefill_jaxpr, decode_jaxpr,
                        prefill_args, decode_args)
        return self._decode

    def paged_programs(self, slots=2, num_pages=4, page_size=4,
                       mem_len=8, steps=2):
        """(prefill_paged_jaxpr, decode_iter_jaxpr, example args) for the
        continuous-batching programs over a real paged state."""
        if self._paged is not None:
            return self._paged
        import jax
        import numpy as np

        eng = self.infer_engine
        state = eng.init_paged_state(slots, num_pages, page_size, mem_len)
        src = np.zeros((slots, mem_len), np.int32)
        vl = np.full((slots,), mem_len, np.int32)
        slot_ids = np.arange(slots, dtype=np.int32)
        first_pages = np.ones((slots,), np.int32)
        active = np.ones((slots,), bool)
        seed = np.int32(0)
        temp = np.float32(1.0)
        pfn = eng._get_paged_prefill_fn("greedy", 0)
        pargs = (eng._values, state, src, vl, slot_ids, first_pages,
                 active, seed, temp)
        prefill_jaxpr = jax.make_jaxpr(pfn)(*pargs)
        tables = np.zeros((slots, 2), np.int32)
        tokens = np.zeros((slots,), np.int32)
        lengths = np.ones((slots,), np.int32)
        dfn = eng._get_decode_iter_fn(steps, "greedy", 0)
        dargs = (eng._values, state, tables, tokens, lengths, active,
                 seed, temp)
        decode_jaxpr = jax.make_jaxpr(dfn)(*dargs)
        self._paged = (prefill_jaxpr, decode_jaxpr, pargs, dargs)
        return self._paged
