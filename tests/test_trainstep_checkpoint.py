"""TrainStep resumability.

The contract: a training run killed at step N and restored in a FRESH
process continues bit-compatibly — parameter values, optimizer moments,
the device-carried PRNG key and step counter all survive; under TP
sharding no process ever writes or reads a full copy of a sharded
array. Reference analogues: Trainer.save_states/load_states +
Module.save_checkpoint (``python/mxnet/gluon/trainer.py`` [unverified]),
extended with the SURVEY §5 "tensorstore-style" sharded layout.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, optimizer as opt, parallel
from mxnet_tpu.gluon import nn

rng = np.random.RandomState(3)
X = rng.randn(32, 16).astype("float32")
Y = rng.randn(32, 1).astype("float32")


def _build(seed=11):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"), nn.Dense(1))
    net.initialize()
    net(mx.nd.array(X))
    return net


def _mesh(shape, names):
    devs = np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, names)


def _params(step):
    step.sync_params()
    s = step._struct_names()
    return {s[k]: v.data().asnumpy() for k, v in
            step._net.collect_params().items()}


TP_RULES = [(r"dense0.*weight", P("model", None)),
            (r"dense1.*weight", P(None, "model"))]


def _make_step(mesh=None, rules=(), seed=11):
    net = _build(seed)
    return parallel.TrainStep(
        net, gluon.loss.L2Loss(), opt.Adam(learning_rate=0.01),
        mesh=mesh, param_rules=rules)


def _run(step, n):
    for _ in range(n):
        L = step(mx.nd.array(X), mx.nd.array(Y))
    return L.asscalar()


def test_state_dict_roundtrip_single_device():
    """3 steps + save + fresh TrainStep + load + 3 steps == 6 straight."""
    ref = _make_step()
    _run(ref, 6)
    want = _params(ref)

    a = _make_step()
    _run(a, 3)
    sd = a.state_dict()
    b = _make_step(seed=99)  # different init: restore must overwrite all
    b.load_state_dict(sd)
    _run(b, 3)
    got = _params(b)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-6, atol=1e-7,
                                   err_msg=k)


def test_sharded_checkpoint_dp_tp_mesh(tmp_path):
    """Save under a dp=4 x tp=2 mesh, restore into a FRESH TrainStep on
    the same mesh, continue: matches the uninterrupted run. The on-disk
    pieces of TP-sharded weights must each be PARTIAL (no full-array
    write anywhere)."""
    mesh = _mesh((4, 2), ("data", "model"))
    ref = _make_step(mesh, TP_RULES)
    _run(ref, 6)
    want = _params(ref)

    a = _make_step(mesh, TP_RULES)
    _run(a, 3)
    a.save_checkpoint(str(tmp_path), step=3)

    # sharded layout honesty: every piece of a model-sharded param covers
    # strictly less than the full var; pieces tile it exactly
    with open(tmp_path / "step_3" / "index_p0.json") as f:
        index = json.load(f)
    shapes = {n: v.data().shape
              for n, v in a._net.collect_params().items()}
    tp_name = [n for n in shapes if "dense0" in n and "weight" in n][0]
    tp_struct = a._struct_names()[tp_name]
    pieces = [e for e in index if e["name"] == f"values/{tp_struct}"]
    assert len(pieces) == 2  # tp=2 distinct shards
    full = shapes[tp_name]
    for e in pieces:
        vol = np.prod([b[1] - b[0] for b in e["bounds"]])
        assert vol < np.prod(full)
    assert sum(np.prod([b[1] - b[0] for b in e["bounds"]])
               for e in pieces) == np.prod(full)

    b = _make_step(mesh, TP_RULES, seed=99)
    extra = b.load_checkpoint(str(tmp_path), step=3)
    assert extra["t_host"] == 3
    _run(b, 3)
    got = _params(b)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)
    # restored opt state is placed per the step's rules, not replicated
    b_tp = {v: k for k, v in b._struct_names().items()}[tp_struct]
    st = b._opt_state[b_tp][0]
    assert not st.sharding.is_fully_replicated


def test_restore_onto_different_mesh(tmp_path):
    """Resharding restore: save from dp4xtp2, restore onto dp2xtp4 and
    onto a single device; both continue to the same result."""
    mesh_a = _mesh((4, 2), ("data", "model"))
    ref = _make_step(mesh_a, TP_RULES)
    _run(ref, 6)
    want = _params(ref)

    a = _make_step(mesh_a, TP_RULES)
    _run(a, 3)
    a.save_checkpoint(str(tmp_path / "ck"))

    mesh_b = _mesh((2, 4), ("data", "model"))
    b = _make_step(mesh_b, TP_RULES, seed=99)
    b.load_checkpoint(str(tmp_path / "ck"))
    _run(b, 3)
    got_b = _params(b)

    c = _make_step(seed=98)  # no mesh at all
    c.load_checkpoint(str(tmp_path / "ck"))
    _run(c, 3)
    got_c = _params(c)

    for k in want:
        np.testing.assert_allclose(got_b[k], want[k], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got_c[k], want[k], rtol=1e-5, atol=1e-6)


def test_restore_in_fresh_process(tmp_path):
    """The literal scenario: kill after 3 steps, restore in a
    brand-new python process, run 3 more, compare to 6 uninterrupted."""
    ref = _make_step(_mesh((4, 2), ("data", "model")), TP_RULES)
    _run(ref, 6)
    want = _params(ref)

    a = _make_step(_mesh((4, 2), ("data", "model")), TP_RULES)
    _run(a, 3)
    a.save_checkpoint(str(tmp_path / "ck"))

    script = tmp_path / "resume.py"
    script.write_text(f"""
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
import numpy as np
import sys
sys.path.insert(0, {str(os.getcwd())!r})
from tests.test_trainstep_checkpoint import (_make_step, _mesh, _run,
                                             _params, TP_RULES)
step = _make_step(_mesh((4, 2), ("data", "model")), TP_RULES, seed=99)
step.load_checkpoint({str(tmp_path / "ck")!r})
_run(step, 3)
np.savez({str(tmp_path / "out.npz")!r}, **_params(step))
""")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, str(script)], cwd=os.getcwd(),
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    got = np.load(tmp_path / "out.npz")
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)


def test_uncommitted_checkpoint_rejected(tmp_path):
    a = _make_step()
    _run(a, 1)
    a.save_checkpoint(str(tmp_path / "ck"))
    os.remove(tmp_path / "ck" / "DONE.p0")
    b = _make_step(seed=99)
    with pytest.raises(mx.base.MXNetError, match="not committed"):
        b.load_checkpoint(str(tmp_path / "ck"))


def test_trainer_interop_roundtrip():
    """Moments cross between the fused step and the eager Trainer: 3
    fused steps -> export -> 3 Trainer steps matches 6 fused steps; and
    3 Trainer steps -> import -> 3 fused steps matches too."""
    ref = _make_step()
    _run(ref, 6)
    want = _params(ref)

    # fused -> Trainer
    a = _make_step()
    _run(a, 3)
    a.sync_params()
    trainer = gluon.Trainer(a._net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    a.export_trainer_states(trainer)
    loss_fn = gluon.loss.L2Loss()
    for _ in range(3):
        with autograd.record():
            L = loss_fn(a._net(mx.nd.array(X)), mx.nd.array(Y))
        L.backward()
        trainer.step(len(X))
    s = a._struct_names()
    got = {s[k]: v.data().asnumpy() for k, v in
           a._net.collect_params().items()}
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)

    # Trainer -> fused
    net = _build()
    trainer2 = gluon.Trainer(net.collect_params(), "adam",
                             {"learning_rate": 0.01})
    for _ in range(3):
        with autograd.record():
            L = loss_fn(net(mx.nd.array(X)), mx.nd.array(Y))
        L.backward()
        trainer2.step(len(X))
    b = parallel.TrainStep(net, gluon.loss.L2Loss(),
                           opt.Adam(learning_rate=0.01))
    b.import_trainer_states(trainer2)
    assert b._t == 3
    _run(b, 3)
    got2 = _params(b)
    for k in want:
        np.testing.assert_allclose(got2[k], want[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)


def test_checkpoint_facade_with_trainstep(tmp_path):
    """checkpoint.save_checkpoint(train_step=...) composes the sharded
    TrainStep layout with the commit-marker step directory, and
    CheckpointManager-style latest_step discovery still works."""
    from mxnet_tpu import checkpoint as ck

    mesh = _mesh((4, 2), ("data", "model"))
    ref = _make_step(mesh, TP_RULES)
    _run(ref, 6)
    want = _params(ref)

    a = _make_step(mesh, TP_RULES)
    _run(a, 3)
    ck.save_checkpoint(str(tmp_path), 3, train_step=a)
    assert ck.latest_step(str(tmp_path)) == 3

    b = _make_step(mesh, TP_RULES, seed=99)
    meta = ck.load_checkpoint(str(tmp_path), train_step=b)
    assert meta["step"] == 3 and meta["has_trainstep"]
    _run(b, 3)
    got = _params(b)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6)


def test_state_dict_survives_donation():
    """state_dict must snapshot: the live buffers are donated to XLA by
    the next step, and the saved dict must not die with them."""
    a = _make_step()
    _run(a, 2)
    sd = a.state_dict()
    _run(a, 2)  # donates the buffers state_dict saw
    # every leaf still readable
    for v in sd["values"].values():
        np.asarray(v)
    for st in sd["opt_state"].values():
        for x in st:
            np.asarray(x)
    np.asarray(sd["key"])
    np.asarray(sd["t_dev"])

    b = _make_step(seed=99)
    b.load_state_dict(sd)
    assert b._t == 2


def test_facade_rejects_missing_trainstep_payload(tmp_path):
    """Loading train_step from a checkpoint saved without one must be a
    clean MXNetError, not a FileNotFoundError."""
    from mxnet_tpu import checkpoint as ck

    net = _build()
    ck.save_checkpoint(str(tmp_path), 1, net=net)
    b = _make_step(seed=99)
    with pytest.raises(mx.base.MXNetError, match="without a TrainStep"):
        ck.load_checkpoint(str(tmp_path), train_step=b)


def test_partial_shard_write_not_latest(tmp_path):
    """A step whose sharded payload lacks a process's DONE marker must
    be invisible to latest_step (restart falls back to the older good
    step instead of wedging)."""
    from mxnet_tpu import checkpoint as ck

    a = _make_step()
    _run(a, 1)
    ck.save_checkpoint(str(tmp_path), 1, train_step=a)
    _run(a, 1)
    ck.save_checkpoint(str(tmp_path), 2, train_step=a)
    os.remove(tmp_path / "step_2" / "trainstep" / "DONE.p0")
    assert ck.latest_step(str(tmp_path)) == 1
    b = _make_step(seed=99)
    meta = ck.load_checkpoint(str(tmp_path), train_step=b)
    assert meta["step"] == 1


def test_manager_rolls_trainstep_checkpoints(tmp_path):
    from mxnet_tpu import checkpoint as ck

    mgr = ck.CheckpointManager(str(tmp_path), keep=2)
    a = _make_step()
    for s in (1, 2, 3):
        _run(a, 1)
        mgr.save(s, train_step=a)
    assert not (tmp_path / "step_1").exists()
    b = _make_step(seed=99)
    meta = mgr.restore_latest(train_step=b)
    assert meta["step"] == 3 and b._t == 3
