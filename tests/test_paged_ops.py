"""``ops/paged.py``: the one place that decides which form of dense paged
attention runs, and the rules that keep it the one place.

- no net asks whether kernels run or reaches past ``ops/`` for a paged
  attention kernel; no kernel module takes a private name from a sibling;
- ``decode_attention`` and ``window_attention`` with the kernels forced
  (interpreted here) against their ``jax.numpy`` halves, on the layouts the
  three dense nets keep their pools in;
- the fixture that sets the predicate's answer (``paged_kernels``).
"""

import ast
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 - the package sets JAX up
from mxnet_tpu.ops import paged

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
ZOO = os.path.join(REPO, "mxnet_tpu", "gluon", "model_zoo")
PALLAS = os.path.join(REPO, "mxnet_tpu", "ops", "pallas")
NETS = ["granite_hybrid", "ouro", "zaya", "keye", "joyai"]


# ------------------------------------------------------------- the layering
@pytest.mark.parametrize("net", NETS)
def test_a_net_neither_asks_nor_reaches_past_ops(net):
    """A net says what it attends over: it imports no paged attention
    kernel module and never names the predicate."""
    with open(os.path.join(ZOO, net + ".py")) as f:
        text = f.read()
    assert not re.search(r"kernels_on|flash_paged_enabled", text)
    for node in ast.walk(ast.parse(text)):
        if isinstance(node, ast.ImportFrom):
            names = [(node.module or "") + "." + a.name for a in node.names]
        elif isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        else:
            continue
        assert not [n for n in names if "paged_flash_attention" in n], \
            (net, node.lineno)


def _pallas_modules():
    for name in sorted(os.listdir(PALLAS)):
        if name.endswith(".py") and name != "__init__.py":
            with open(os.path.join(PALLAS, name)) as f:
                yield name, f.read()


def test_no_kernel_module_takes_a_private_name_from_a_sibling():
    """What two kernel files share is public where it lives
    (``page_walk.py``, ``index_select.py``); the package's own
    ``_use_interpret`` / ``_partitionable`` are not a sibling's."""
    taken = [(name, node.module, a.name)
             for name, text in _pallas_modules()
             for node in ast.walk(ast.parse(text))
             if isinstance(node, ast.ImportFrom) and node.module
             for a in node.names if a.name.startswith("_")]
    assert not taken


def test_the_walks_block_and_the_lane_width_are_defined_once():
    defs = {what: [name for name, text in _pallas_modules()
                   if re.search(pattern, text, re.M)]
            for what, pattern in [
                ("decode_tiles", r"^def decode_tiles\("),
                ("lanes", r"^_?LANES = ")]}
    assert defs == {"decode_tiles": ["page_walk.py"],
                    "lanes": ["page_walk.py"]}


# ------------------------------------------------- forms against each other
PAGE, P = 8, 3
# name: (query heads, key/value heads, head size, pool declared by head,
#        planes flattened into the pool's pages)
LAYOUTS = {
    "granite": (32, 8, 64, True, 1),
    "ouro": (16, 16, 128, True, 3),
    "zaya": (8, 2, 128, False, 1),
}


def _cache(layout, rng, rows):
    """Pools of ``rows`` rows of ``P`` pages behind trash page 0 (of every
    plane), a shuffled page table, and the plane the tables point into."""
    Hq, Hkv, D, by_head, planes = LAYOUTS[layout]
    pages = 1 + rows * P
    shape = (planes * pages, PAGE, Hkv, D) if by_head \
        else (planes * pages, PAGE * Hkv, D)
    kp = jnp.asarray(rng.normal(size=shape), jnp.float32)
    vp = jnp.asarray(rng.normal(size=shape), jnp.float32)
    table = 1 + rng.permutation(rows * P).reshape(rows, P)
    table[-1] = 0                        # an inactive row: the trash page
    start = (planes - 1) * pages         # the last plane's pages
    return kp, vp, jnp.asarray(table + start, jnp.int32), \
        None if by_head else Hkv


@pytest.mark.parametrize("boundary", ["on-a-page-boundary", "inside-a-page"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_decode_attention_kernels_against_the_gather_form(
        layout, boundary, paged_kernels):
    """One query a row over the three nets' pools: the kernel the chip runs
    (the walk at heads of 128, the window at heads of 64) and the gather by
    token agree, for rows at a page's first, last and middle positions and
    for a row that is inactive (its table the trash page)."""
    Hq, Hkv, D, _, _ = LAYOUTS[layout]
    rng = np.random.default_rng(len(layout))
    B = 4
    kp, vp, table, kv_heads = _cache(layout, rng, B)
    pos = {"on-a-page-boundary": [PAGE - 1, PAGE, 2 * PAGE, 0],
           "inside-a-page": [3, PAGE + 2, P * PAGE - 2, 5]}[boundary]
    pos = jnp.asarray(pos, jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)

    def run():      # another callable a form: a trace is cached by it
        return jax.jit(lambda *a: paged.decode_attention(
            *a, D ** -0.5, kv_heads))(q, kp, vp, table, pos)

    paged_kernels(False)
    want = np.asarray(run())
    paged_kernels(True)
    got = np.asarray(run())
    assert got.shape == (B, Hq * D) and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("real", [[16, 16], [11, 0]],
                         ids=["whole-chunks", "a-ragged-and-an-empty-row"])
def test_window_attention_kernel_against_the_flash_loop(real, paged_kernels):
    """A chunk of 16 queries a row over zaya's pools (``(pages, page x 2,
    128)``): the paged window kernel in blocks of queries and the
    ``jax.numpy`` flash loop agree on every real query."""
    Hq, Hkv, D, _, _ = LAYOUTS["zaya"]
    rng = np.random.default_rng(5)
    R, C = 2, 16
    kp, vp, table, kv_heads = _cache("zaya", rng, R + 1)
    table = table[:R]
    q = jnp.asarray(rng.normal(size=(R, C, Hq, D)), jnp.float32)
    off = jnp.asarray([PAGE, 3], jnp.int32)
    real = jnp.asarray(real, jnp.int32)

    def run():
        return jax.jit(lambda *a: paged.window_attention(
            *a, D ** -0.5, kv_heads, 8))(q, kp, vp, table, off, real)

    paged_kernels(False)
    want = np.asarray(run())
    paged_kernels(True)
    got = np.asarray(run())
    live = np.arange(C)[None] < np.asarray(real)[:, None]
    assert got.shape == (R, C, Hq * D) and live.any()
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)


# -------------------------------------------------------------- the fixture
@pytest.mark.parametrize("on", [True, False], ids=["on", "off"])
def test_the_fixture_decides_which_form_a_trace_holds(on, paged_kernels):
    """``paged_kernels(on)`` is the whole of the steering: the traced
    ``decode_attention`` holds the kernel's call, or the gather."""
    assert not paged.kernels_on()        # the CPU's own answer
    paged_kernels(on)
    assert paged.kernels_on() is on
    rng = np.random.default_rng(1)
    kp, vp, table, _ = _cache("granite", rng, 2)
    Hq, _, D, _, _ = LAYOUTS["granite"]
    text = str(jax.make_jaxpr(lambda q, pos: paged.decode_attention(
        q, kp, vp, table, pos, D ** -0.5))(
            jnp.zeros((2, Hq, D)), jnp.zeros((2,), jnp.int32)))
    assert ("paged_window" in text) == on
    assert ("gather" in text) != on
