"""Shape-stable execution: persistent compilation cache, AOT warmup,
recompile guard, and the tier-1 compile-count lint."""

import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache
from mxnet_tpu.base import MXNetError


def _counters():
    return mx.telemetry.registry().snapshot()["counters"]


class TestRecompileGuard:
    def test_counts_distinct_signatures(self):
        g = compile_cache.RecompileGuard("t")
        assert g.observe(("a",)) is True
        assert g.observe(("a",)) is False
        assert g.observe(("b",)) is True
        assert g.signatures == 2
        assert g.steady_state_recompiles == 0

    def test_steady_state_recompile_warns(self):
        g = compile_cache.RecompileGuard("t")
        g.observe(("a",))
        g.mark_steady()
        with pytest.warns(RuntimeWarning, match="shape-churn"):
            g.observe(("b",))
        assert g.steady_state_recompiles == 1

    def test_limit_raises(self, monkeypatch):
        monkeypatch.setenv("MXTPU_RECOMPILE_LIMIT", "0")
        g = compile_cache.RecompileGuard("t")
        g.observe(("a",))
        g.mark_steady()
        with pytest.raises(MXNetError, match="MXTPU_RECOMPILE_LIMIT"):
            g.observe(("b",))

    def test_negative_limit_silences(self, monkeypatch):
        import warnings

        monkeypatch.setenv("MXTPU_RECOMPILE_LIMIT", "-1")
        g = compile_cache.RecompileGuard("t")
        g.observe(("a",))
        g.mark_steady()
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            g.observe(("b",))  # counted, not warned
        assert g.steady_state_recompiles == 1

    def test_unbounded_signature_warning(self, monkeypatch):
        monkeypatch.setenv("MXTPU_RECOMPILE_LIMIT", "3")
        g = compile_cache.RecompileGuard("t")
        with pytest.warns(RuntimeWarning, match="staged signatures"):
            for i in range(5):
                g.observe((i,))

    def test_info_summaries(self):
        g = compile_cache.RecompileGuard("t")
        g.observe(("a",), "sigA")
        g.observe(("a",))
        info = g.info()
        assert info["signatures"] == 1
        assert info["entries"][0]["signature"] == "sigA"
        assert info["entries"][0]["count"] == 2


def _tiny_step(donate=True):
    from mxnet_tpu import gluon, nd, optimizer as opt
    from mxnet_tpu.parallel import TrainStep

    net = gluon.nn.Dense(4)
    net.initialize()
    net(nd.zeros((2, 8)))
    return TrainStep(net, gluon.loss.L2Loss(),
                     opt.SGD(learning_rate=0.1), donate=donate)


class TestTrainStepWarmup:
    def test_warmup_then_zero_recompiles(self):
        import jax

        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda e, d, **kw: compiles.append(e)
            if "backend_compile" in e else None)
        step = _tiny_step()
        sigs = [(((4, 8), "float32"), ((4, 4), "float32")),
                (((8, 8), "float32"), ((8, 4), "float32"))]
        assert step.warmup(sigs) == 2
        assert step.compile_guard.steady
        assert step.compile_guard.signatures == 2
        x4 = mx.nd.array(np.zeros((4, 8), "float32"))
        y4 = mx.nd.array(np.zeros((4, 4), "float32"))
        x8 = mx.nd.array(np.zeros((8, 8), "float32"))
        y8 = mx.nd.array(np.zeros((8, 4), "float32"))
        float(x4.sum().asscalar())  # retire eager array setup compiles
        n0 = len(compiles)
        for _ in range(2):
            step(x4, y4)
            step(x8, y8)
        assert step.compile_guard.steady_state_recompiles == 0
        assert len(compiles) == n0, "post-warmup steps recompiled"

    def test_warmup_duplicate_signatures_compile_once(self):
        step = _tiny_step()
        sig = (((4, 8), "float32"), ((4, 4), "float32"))
        assert step.warmup([sig, sig]) == 1

    def test_warmup_preserves_training_state(self):
        step = _tiny_step()
        before = {n: np.asarray(v)
                  for n, v in step._values.items()}
        t_before = step._t
        step.warmup([(((4, 8), "float32"), ((4, 4), "float32"))])
        for n, v in step._values.items():
            assert np.asarray(v).tobytes() == before[n].tobytes(), n
        assert step._t == t_before

    def test_warmed_and_cold_first_losses_match(self):
        rng = np.random.RandomState(3)
        x = rng.randn(4, 8).astype("float32")
        y = rng.randn(4, 4).astype("float32")

        def first_loss(warm):
            mx.random.seed(11)
            np.random.seed(11)
            step = _tiny_step()
            if warm:
                step.warmup([(((4, 8), "float32"), ((4, 4), "float32"))])
            return float(step(mx.nd.array(x), mx.nd.array(y)).asscalar())

        assert first_loss(False) == first_loss(True)

    def test_accum_split_signatures(self):
        from mxnet_tpu import gluon, nd, optimizer as opt
        from mxnet_tpu.parallel import TrainStep

        net = gluon.nn.Dense(4)
        net.initialize()
        net(nd.zeros((2, 8)))
        step = TrainStep(net, gluon.loss.L2Loss(),
                         opt.SGD(learning_rate=0.1), grad_accum=2)
        step.warmup([(((8, 8), "float32"), ((8, 4), "float32"))])
        step(mx.nd.array(np.zeros((8, 8), "float32")),
             mx.nd.array(np.zeros((8, 4), "float32")))
        assert step.compile_guard.signatures == 1
        assert step.compile_guard.steady_state_recompiles == 0

    def test_steady_recompile_raises_under_limit(self, monkeypatch):
        monkeypatch.setenv("MXTPU_RECOMPILE_LIMIT", "0")
        step = _tiny_step()
        step.warmup([(((4, 8), "float32"), ((4, 4), "float32"))])
        with pytest.raises(MXNetError, match="MXTPU_RECOMPILE_LIMIT"):
            step(mx.nd.array(np.zeros((6, 8), "float32")),
                 mx.nd.array(np.zeros((6, 4), "float32")))

    def test_cache_info(self):
        step = _tiny_step()
        step(mx.nd.array(np.zeros((4, 8), "float32")),
             mx.nd.array(np.zeros((4, 4), "float32")))
        info = step.cache_info()
        assert info["signatures"] == 1
        assert "float32[4x8]" in info["entries"][0]["signature"]


class TestCachedOpWarmup:
    def _net(self):
        from mxnet_tpu import gluon, nd

        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(8, activation="relu"),
                    gluon.nn.Dense(4))
        net.initialize()
        net.hybridize()
        net(nd.zeros((2, 6)))
        return net

    def test_forward_warmup_then_zero_recompiles(self):
        from mxnet_tpu import nd

        net = self._net()
        co = net._cached_op
        assert co.warmup((((4, 6), "float32"),)) == 1
        net(nd.zeros((4, 6)))
        assert co._guard.steady_state_recompiles == 0

    def test_backward_warmup_covers_recorded_path(self):
        from mxnet_tpu import autograd, nd

        net = self._net()
        co = net._cached_op
        co.warmup((((4, 6), "float32"),), backward=True)
        x = nd.zeros((4, 6))
        x.attach_grad()
        with autograd.record():
            y = net(x).sum()
        y.backward()
        assert co._guard.steady_state_recompiles == 0

    def test_cache_info_tracks_modes(self):
        from mxnet_tpu import nd

        net = self._net()
        co = net._cached_op
        co.warmup((((4, 6), "float32"),), backward=True)
        info = co.cache_info()
        sigs = [e["signature"] for e in info["entries"]]
        assert any("train vjp" in s for s in sigs)
        assert any("train fwd" in s for s in sigs)
        assert info["staged_programs"] >= 1


class TestEstimatorWarmup:
    def test_fit_warmup_true_precompiles_loader_shapes(self):
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon.contrib.estimator import Estimator

        rng = np.random.RandomState(0)
        ds = [(rng.rand(6).astype("float32"),
               rng.rand(4).astype("float32")) for _ in range(12)]
        loader = gluon.data.DataLoader(ds, batch_size=4)
        net = gluon.nn.Dense(4)
        net.initialize()
        est = Estimator(net, gluon.loss.L2Loss())
        before = _counters().get("compile/warmup_compiles", 0)
        est.fit(loader, epochs=1, warmup=True)
        assert _counters()["compile/warmup_compiles"] == before + 1

    def test_fit_warmup_explicit_signatures(self):
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon.contrib.estimator import Estimator

        rng = np.random.RandomState(0)
        ds = [(rng.rand(6).astype("float32"),
               rng.rand(4).astype("float32")) for _ in range(8)]
        loader = gluon.data.DataLoader(ds, batch_size=4)
        net = gluon.nn.Dense(4)
        net.initialize()
        est = Estimator(net, gluon.loss.L2Loss())
        before = _counters().get("compile/warmup_compiles", 0)
        est.fit(loader, epochs=1,
                warmup=[(((4, 6), "float32"), ((4, 4), "float32"))])
        assert _counters()["compile/warmup_compiles"] == before + 1

    def test_fit_warmup_marks_hybridized_guard_steady(self):
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon.contrib.estimator import Estimator

        rng = np.random.RandomState(0)
        ds = [(rng.rand(6).astype("float32"),
               rng.rand(4).astype("float32")) for _ in range(8)]
        loader = gluon.data.DataLoader(ds, batch_size=4)
        net = gluon.nn.Dense(4)
        net.initialize()
        net.hybridize()
        est = Estimator(net, gluon.loss.L2Loss())
        est.fit(loader, epochs=1, warmup=True)
        assert net._cached_op is not None
        assert net._cached_op._guard.steady


_CHILD = r"""
import jax, jax.numpy as jnp
import mxnet_tpu as mx
f = jax.jit(lambda x: (x * 3 + 1).sum())
f(jnp.arange(16.0))
s = mx.compile_cache.cache_stats()
print("STATS", s["enabled"], s["hits"], s["misses"])
"""


class TestPersistentCache:
    def test_env_setup_modes(self, monkeypatch):
        assert compile_cache.recompile_limit() is None or isinstance(
            compile_cache.recompile_limit(), int)
        # default-on (set up at import)
        assert compile_cache.is_enabled()
        assert compile_cache.cache_dir()

    def test_subprocess_warm_start_hits(self, tmp_path):
        env = dict(os.environ)
        env.pop("MXTPU_COMPILE_CACHE_DIR", None)
        # the directory is placed from outside through JAX's own
        # variables; zero thresholds persist even this tiny program
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
        env["JAX_PLATFORMS"] = "cpu"
        outs = []
        for _ in range(2):
            r = subprocess.run([sys.executable, "-c", _CHILD],
                               capture_output=True, text=True, env=env,
                               timeout=240, cwd=os.path.dirname(
                                   os.path.dirname(
                                       os.path.abspath(__file__))))
            assert r.returncode == 0, r.stderr[-2000:]
            line = [ln for ln in r.stdout.splitlines()
                    if ln.startswith("STATS")][0]
            outs.append(line.split())
        first, second = outs
        assert first[1] == "True"
        assert int(first[3]) > 0, "first process should miss (and write)"
        assert int(second[2]) > 0, "second process should hit the cache"


class TestTelemetrySurface:
    def test_report_carries_compile_family(self):
        rep = mx.telemetry.report()
        for k in ("compile_signatures", "compile_steady_state_recompiles",
                  "compile_warmup_compiles", "compile_cache_hits",
                  "compile_cache_misses"):
            assert k in rep

    def test_telemetry_report_tool_prints_compile_family(self, tmp_path,
                                                         capsys):
        import json
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        try:
            import telemetry_report
        finally:
            sys.path.pop(0)
        (tmp_path / "events.jsonl").write_text(
            '{"ph": "X", "name": "estimator.epoch", "dur": 1000}\n')
        (tmp_path / "report.json").write_text(json.dumps({
            "counters": {"compile/signatures": 5,
                         "compile/steady_state_recompiles": 2,
                         "compile/cache_hits": 3},
            "gauges": {"compile/persistent_cache_enabled": 1},
            "histograms": {"jax/compile_time_s":
                           {"sum": 1.5, "count": 4}},
        }))
        telemetry_report.main([str(tmp_path)])
        out = capsys.readouterr().out
        assert "Compile (shape stability)" in out
        assert "compile/signatures" in out
        assert "WARNING: 2 steady-state recompile(s)" in out
