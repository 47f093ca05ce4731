"""Tier-1 wiring for mxlint, the unified static-analysis framework
(``mxnet_tpu/analysis/`` + ``tools/mxlint.py``).

Absorbs the three pre-framework lint tests — test_no_sync_lint.py,
test_amp_purity.py, test_sharding_lint.py — keeping their full case
coverage, and adds the violation self-tests for the four new passes
(lock-order, donation, recompile-hazard, collective-placement) plus the
two consistency passes (env-vars, telemetry-names): every pass gets a
seeded positive control (synthetic deadlock cycle, use-after-donate,
recompile hazard, unguarded host allreduce...) and a clean negative
control, and the WHOLE suite must run green at HEAD (modulo the
committed baseline) inside the runtime budget.
"""

import json
import os
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(REPO, "tools"))

from mxnet_tpu.analysis import (  # noqa: E402
    Baseline, Context, Finding, all_passes, get_pass, run_passes,
)
from mxnet_tpu.analysis import ast_driver, jaxpr_driver  # noqa: E402
from mxnet_tpu.analysis import callgraph  # noqa: E402
from mxnet_tpu.analysis.passes import (  # noqa: E402
    amp_purity, collectives, donation, env_vars, lock_order, no_sync,
    recompile, resource_leak, rpc_protocol, sharding_placement,
    swap_barrier, telemetry_names,
)

BASELINE_PATH = os.path.join(REPO, "tools", "mxlint_baseline.json")

ALL_PASSES = {"no-sync", "amp-purity", "sharding-placement", "lock-order",
              "donation", "recompile-hazard", "collective-placement",
              "env-vars", "telemetry-names", "resource-leak",
              "rpc-protocol", "swap-barrier"}


@pytest.fixture(scope="module")
def ctx():
    """One shared Context: the jaxpr passes reuse its cached real
    TrainStep/InferStep programs (built once per module)."""
    return Context()


@pytest.fixture(scope="module")
def sharding_setup():
    return sharding_placement.build_default_setup()


def _write_module(tmp_path, source, name="mod.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return ast_driver.AstIndex(str(tmp_path)), name


# ================================================================ framework
class TestFramework:
    def test_registry_has_the_full_roster(self):
        assert set(all_passes()) == ALL_PASSES

    def test_fingerprint_excludes_line_numbers(self):
        a = Finding("p", "r", "x/y.py", 10, "K", "m1")
        b = Finding("p", "r", "x/y.py", 99, "K", "reworded")
        assert a.fingerprint == b.fingerprint
        assert a.fingerprint != Finding("p", "r", "x/y.py", 10, "K2",
                                        "m1").fingerprint

    def test_baseline_requires_reasons(self, tmp_path):
        p = tmp_path / "b.json"
        p.write_text(json.dumps({"entries": {"x": {"reason": ""}}}))
        with pytest.raises(ValueError):
            Baseline.load(str(p))

    def test_baseline_suppresses_by_fingerprint(self):
        f = Finding("p", "r", "x.py", 1, "K", "m")
        b = Baseline({f.fingerprint: {"reason": "known"}})
        assert b.reason(f) == "known"
        assert b.reason(Finding("p", "r", "x.py", 1, "other", "m")) is None

    def test_full_suite_green_at_head_within_budget(self, ctx):
        """THE acceptance gate: all passes (including the three
        interprocedural ones), real programs, committed baseline — zero
        unbaselined findings, zero stale baseline entries, under the
        90 s budget."""
        t0 = time.perf_counter()
        baseline = Baseline.load(BASELINE_PATH)
        findings, suppressed = run_passes(baseline=baseline, ctx=ctx)
        elapsed = time.perf_counter() - t0
        assert not findings, "\n".join(repr(f) for f in findings)
        for f, reason in suppressed:
            assert reason.strip()
        # the baseline file stays honest: every entry matched a finding
        matched = {f.fingerprint for f, _ in suppressed}
        stale = set(baseline.entries) - matched
        assert not stale, f"stale baseline entries: {sorted(stale)}"
        # and the ISSUE-15 passes grandfathered NOTHING: the serving
        # plane is clean under the interprocedural model at head
        assert not any(
            e.get("pass") in ("resource-leak", "rpc-protocol",
                              "swap-barrier")
            for e in baseline.entries.values())
        assert elapsed < 90.0, f"lint suite took {elapsed:.1f}s"

    def test_cli_json_output(self, capsys):
        import mxlint

        rc = mxlint.main(["--passes", "no-sync,env-vars,telemetry-names",
                          "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0 and out["ok"] is True
        assert out["passes_run"] == ["no-sync", "env-vars",
                                     "telemetry-names"]

    def test_cli_lists_passes(self, capsys):
        import mxlint

        assert mxlint.main(["--list"]) == 0
        listed = capsys.readouterr().out
        for name in ALL_PASSES:
            assert name in listed

    def test_cli_stale_baseline_fails_then_prunes(self, tmp_path,
                                                  capsys):
        """A baseline entry matching no finding fails the default run
        (exit 1) and --prune-baseline deletes exactly it."""
        import mxlint

        bl = json.loads(open(BASELINE_PATH).read())
        stale_fp = ("lock-order.shared-state:"
                    "mxnet_tpu/serving/batcher.py:Gone.attr")
        bl["entries"][stale_fp] = {
            "reason": "code this excused was deleted", "pass":
            "lock-order", "rule": "shared-state",
            "path": "mxnet_tpu/serving/batcher.py"}
        p = tmp_path / "b.json"
        p.write_text(json.dumps(bl))
        rc = mxlint.main(["--passes", "lock-order",
                          "--baseline", str(p)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "STALE" in out and stale_fp in out
        rc = mxlint.main(["--passes", "lock-order", "--baseline",
                          str(p), "--prune-baseline"])
        assert rc == 0
        capsys.readouterr()
        entries = json.loads(p.read_text())["entries"]
        assert stale_fp not in entries
        assert len(entries) == 2  # the real grandfathered pair survives
        assert mxlint.main(["--passes", "lock-order",
                            "--baseline", str(p)]) == 0
        capsys.readouterr()

    def test_cli_stale_scoped_to_executed_passes(self, tmp_path,
                                                 capsys):
        """An entry belonging to a pass we did NOT run is not stale —
        a --passes subset must not invalidate the rest of the file."""
        import mxlint

        bl = json.loads(open(BASELINE_PATH).read())
        bl["entries"]["donation.fake:x.py:K"] = {
            "reason": "other pass", "pass": "donation",
            "rule": "fake", "path": "x.py"}
        p = tmp_path / "b.json"
        p.write_text(json.dumps(bl))
        assert mxlint.main(["--passes", "lock-order",
                            "--baseline", str(p)]) == 0
        capsys.readouterr()

    def test_cli_github_annotations(self, tmp_path, capsys):
        """--github emits one ::error per finding, pinned to file/line
        (and per stale baseline entry); a clean run emits none."""
        import mxlint

        rc = mxlint.main(["--passes", "lock-order", "--baseline",
                          "none", "--github"])
        out = capsys.readouterr().out
        assert rc == 1  # the two baselined races are findings sans file
        lines = [ln for ln in out.splitlines()
                 if ln.startswith("::error ")]
        assert len(lines) == 2
        for ln in lines:
            assert ln.startswith(
                "::error file=mxnet_tpu/serving/batcher.py,line=")
            assert "[lock-order.shared-state]" in ln
        rc = mxlint.main(["--passes", "lock-order", "--github"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "::error" not in out


# ============================================== no-sync (ported coverage)
class TestNoSync:
    def test_fast_path_is_sync_free(self):
        violations = no_sync.find_violations()
        assert not violations, "\n".join(
            f"step.py:{ln}: {msg}" for ln, msg in violations)

    def test_all_hot_paths_are_sync_free(self):
        violations = no_sync.find_all_violations()
        assert not violations, "\n".join(
            f"{path}:{ln}: {msg}" for path, ln, msg in violations)

    def test_targets_cover_inference_engine(self):
        covered = {(os.path.basename(p), cls): set(funcs)
                   for p, cls, funcs in no_sync.TARGETS}
        assert "decode_n" in covered[("infer.py", "InferStep")]
        assert "_dispatch" in covered[("batcher.py", "ContinuousBatcher")]
        # one scheduler: no target names a class that is gone
        assert {cls for _p, cls, _f in no_sync.TARGETS} == \
            {"TrainStep", "InferStep", "ContinuousBatcher"}

    def test_targets_cover_continuous_batching(self):
        covered = {(os.path.basename(p), cls): set(funcs)
                   for p, cls, funcs in no_sync.TARGETS}
        assert "decode_iter" in covered[("infer.py", "InferStep")]
        assert "prefill_paged" in covered[("infer.py", "InferStep")]
        cont = covered[("batcher.py", "ContinuousBatcher")]
        assert "_dispatch" in cont
        assert {"_may_run_ahead", "_dispatch_ahead"} <= cont
        assert "next_carry" in covered[("infer.py", "InferStep")]
        assert "_step_once" in cont  # the scheduler loop body
        # the retire path: a new root's frames never come to the host
        assert {"_retire", "_register_roots", "_store_rows",
                "_register_prefix"} <= cont

    def test_lint_catches_a_violation(self, tmp_path):
        bad = tmp_path / "step_bad.py"
        bad.write_text(
            "class TrainStep:\n"
            "    def __call__(self, x):\n"
            "        return float(self._dispatch(x))\n"
            "    def _dispatch(self, x):\n"
            "        return x.asnumpy()\n"
        )
        violations = no_sync.find_violations(str(bad))
        assert len(violations) == 2
        assert any("float" in m for _, m in violations)
        assert any("asnumpy" in m for _, m in violations)

    def test_lint_catches_decode_violation(self, tmp_path):
        bad = tmp_path / "infer_bad.py"
        bad.write_text(
            "class InferStep:\n"
            "    def decode_n(self, src):\n"
            "        import jax\n"
            "        out = self._fn(src)\n"
            "        jax.block_until_ready(out)\n"
            "        return out\n"
        )
        violations = no_sync.find_violations(
            str(bad), "InferStep", ("decode_n",))
        assert len(violations) == 1
        assert "block_until_ready" in violations[0][1]

    def test_lint_catches_decode_iter_violation(self, tmp_path):
        bad = tmp_path / "infer_bad_paged.py"
        bad.write_text(
            "class InferStep:\n"
            "    def decode_iter(self, state, tables, tokens):\n"
            "        buf, state = self._fn(state, tables, tokens)\n"
            "        return buf.asnumpy(), state\n"
            "    def prefill_paged(self, state, src):\n"
            "        tok0, state = self._fn(state, src)\n"
            "        return int(tok0[0]), state\n"
        )
        violations = no_sync.find_violations(
            str(bad), "InferStep", ("decode_iter", "prefill_paged"))
        assert len(violations) == 2
        assert any("asnumpy" in m for _, m in violations)
        assert any("int" in m for _, m in violations)

    def test_lint_catches_scheduler_loop_violation(self, tmp_path):
        bad = tmp_path / "batcher_bad.py"
        bad.write_text(
            "import time\n"
            "class ContinuousBatcher:\n"
            "    def _step_once(self):\n"
            "        time.sleep(0.01)\n"
            "        return True\n"
            "    def _dispatch(self, live):\n"
            "        out = self._engine.decode_iter(live)\n"
            "        return out[0].tolist()\n"
        )
        violations = no_sync.find_violations(
            str(bad), "ContinuousBatcher", ("_step_once", "_dispatch"))
        assert len(violations) == 2
        assert any("sleep" in m for _, m in violations)
        assert any("tolist" in m for _, m in violations)

    # ---- the second rule set: eager device constructors on a dispatch path
    def test_dispatch_targets_are_linted_targets(self):
        covered = {(p, cls): set(funcs) for p, cls, funcs in no_sync.TARGETS}
        assert "_apply_prefix_hits" in \
            covered[(no_sync.BATCHER_PY, "ContinuousBatcher")]
        for path, cls, funcs in no_sync.DISPATCH_TARGETS:
            assert set(funcs) <= covered[(path, cls)]
        paged = dict(((p, c), f) for p, c, f in no_sync.DISPATCH_TARGETS)
        assert {"decode_iter", "next_carry", "prefill_paged",
                "prefill_suffix_paged", "spec_draft", "spec_verify"} == \
            set(paged[(no_sync.INFER_PY, "InferStep")])
        assert {"_store_rows", "_apply_prefix_hits", "_dispatch",
                "_may_run_ahead", "_dispatch_ahead"} <= \
            set(paged[(no_sync.BATCHER_PY, "ContinuousBatcher")])

    @pytest.mark.parametrize("cls,func,line,rule", [
        # the burst dispatched ahead may not read the burst before it ...
        ("ContinuousBatcher", "_dispatch_ahead",
         "toks = flight.buf.asnumpy()", ".asnumpy()"),
        # ... nor make its operands by an enqueue of its own
        ("ContinuousBatcher", "_dispatch_ahead",
         "tokens = jnp.asarray(flight.lengths)", "jnp.asarray"),
        # the rule is reckoned from state the scheduler holds
        ("ContinuousBatcher", "_may_run_ahead",
         "done = int(flight.buf[0, 0])", "int(...)"),
        # and the program that carries a burst into the next pulls
        # nothing to the host
        ("InferStep", "next_carry",
         "block = np.asarray(block)", "np.asarray"),
        ("InferStep", "next_carry",
         "lengths = jnp.int32(lengths)", "jnp.int32"),
    ])
    def test_lint_covers_the_burst_dispatched_ahead(self, tmp_path, cls,
                                                    func, line, rule):
        """``_dispatch_ahead``, ``_may_run_ahead`` and ``next_carry`` are
        linted targets under both rule sets, as ``_dispatch`` is."""
        path = {"InferStep": no_sync.INFER_PY,
                "ContinuousBatcher": no_sync.BATCHER_PY}[cls]
        for targets in (no_sync.TARGETS, no_sync.DISPATCH_TARGETS):
            assert func in dict(((p, c), f) for p, c, f in targets)[
                (path, cls)]
        bad = tmp_path / "ahead_bad.py"
        bad.write_text(
            "import numpy as np\n"
            "import jax.numpy as jnp\n"
            f"class {cls}:\n"
            f"    def {func}(self, flight, block=None, lengths=None):\n"
            f"        {line}\n"
            "        return self._fn(flight)\n")
        violations = no_sync.find_violations(str(bad), cls, (func,),
                                             (func,))
        assert len(violations) == 1 and rule in violations[0][1]

    def test_clean_dispatch_passes_both_rule_sets(self, tmp_path):
        good = tmp_path / "infer_clean.py"
        good.write_text(
            "import numpy as np\n"
            "import jax\n"
            "import jax.numpy as jnp\n"
            "class InferStep:\n"
            "    @staticmethod\n"
            "    def _operands(dtype, *xs):\n"
            "        return [np.array(x, dtype) for x in xs]\n"
            "    def decode_iter(self, state, tables, tokens, seed):\n"
            "        tables, tokens = self._operands(np.int32, tables,\n"
            "                                        tokens)\n"
            "        return self._fn(state, tables, tokens, seed)\n"
            "    def _program(self, state, tables, tokens, seed):\n"
            "        key = jax.random.PRNGKey(seed)\n"
            "        return jnp.asarray(tokens), key\n"
        )
        assert not no_sync.find_violations(
            str(good), "InferStep", ("decode_iter",), ("decode_iter",))

    @pytest.mark.parametrize("call,shown", [
        ("jnp.asarray(tokens, jnp.int32)", "jnp.asarray"),
        ("jnp.array(tokens)", "jnp.array"),
        ("jnp.float32(temperature)", "jnp.float32"),
        ("jnp.int32(seed)", "jnp.int32"),
        ("jnp.bool_(active)", "jnp.bool_"),
        ("jax.device_put(tokens)", "jax.device_put"),
        ("jax.random.PRNGKey(seed)", "jax.random.PRNGKey"),
        ("jax.random.key(seed)", "jax.random.key"),
    ])
    def test_lint_catches_eager_constructor(self, tmp_path, call, shown):
        bad = tmp_path / "infer_eager.py"
        bad.write_text(
            "import jax\n"
            "import jax.numpy as jnp\n"
            "class InferStep:\n"
            "    def decode_iter(self, state, tokens, seed, temperature,\n"
            "                    active):\n"
            f"        x = {call}\n"
            "        return self._fn(state, x)\n"
        )
        violations = no_sync.find_violations(
            str(bad), "InferStep", ("decode_iter",), ("decode_iter",))
        assert len(violations) == 1
        assert "eager device constructor" in violations[0][1]
        assert shown + "(" in violations[0][1]
        # the first rule set alone (decode_n, TrainStep) does not mind it
        assert not no_sync.find_violations(
            str(bad), "InferStep", ("decode_iter",))

    def test_eager_constructor_is_its_own_rule(self, ctx, monkeypatch,
                                               tmp_path):
        bad = tmp_path / "batcher_eager.py"
        bad.write_text(
            "import jax.numpy as jnp\n"
            "class ContinuousBatcher:\n"
            "    def _store_rows(self, by_row):\n"
            "        return self._store_fn(jnp.asarray(by_row))\n"
        )
        target = ((str(bad), "ContinuousBatcher", ("_store_rows",)),)
        monkeypatch.setattr(no_sync, "TARGETS", target)
        monkeypatch.setattr(no_sync, "DISPATCH_TARGETS", target)
        findings = no_sync.NoSyncPass().run(ctx)
        assert [f.rule for f in findings] == ["eager-constructor"]


# =========================================== amp-purity (ported coverage)
class TestAmpPurity:
    def test_amp_step_has_no_mixed_dots(self, ctx):
        violations = amp_purity.check_step_purity(
            jaxpr=ctx.programs.train_jaxpr)
        assert not violations, "\n".join(violations)

    def test_overflow_skip_path_is_sync_free(self):
        violations = amp_purity.find_overflow_sync_violations()
        assert not violations, "\n".join(
            f"step.py:{ln}: {msg}" for ln, msg in violations)

    def test_lint_detects_a_mixed_dot(self):
        import jax
        import jax.numpy as jnp

        # mixed dot written deliberately: f32 x bf16
        def worse(w32, x16):
            return jax.lax.dot_general(
                w32, x16, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).sum()

        jaxpr = jax.make_jaxpr(worse)(
            jax.ShapeDtypeStruct((4, 8), jnp.float32),
            jax.ShapeDtypeStruct((8, 4), jnp.bfloat16))
        assert jaxpr_driver.find_mixed_dots(jaxpr)

    def test_lint_detects_a_sync_in_traced_closure(self, tmp_path):
        bad = tmp_path / "step_bad.py"
        bad.write_text(
            "class TrainStep:\n"
            "    def _build(self, donate):\n"
            "        n = float(self._optimizer.wd)  # host-side: legal\n"
            "        def step(vals):\n"
            "            return float(vals)  # traced closure: violation\n"
            "        return step\n"
        )
        violations = amp_purity.find_overflow_sync_violations(str(bad))
        assert len(violations) == 1
        assert "float" in violations[0][1]


# ==================================== sharding-placement (ported coverage)
class TestShardingPlacement:
    def test_sharding_lint_passes(self, sharding_setup):
        violations = sharding_placement.run_checks(*sharding_setup)
        assert not violations, "\n".join(violations)

    def test_lint_flags_inert_rule(self, sharding_setup):
        from mxnet_tpu.parallel import sharding as shard
        from mxnet_tpu.parallel import PartitionSpec as P

        mesh, _, _, _, _, shapes = sharding_setup
        bad = shard.ShardingRules.fsdp(min_size=32, rules=[
            (r"matches_nothing$", P("data"))])
        violations = sharding_placement.check_rules_coverage(
            bad, shapes, mesh)
        assert any("matched NO parameter" in v for v in violations)

    def test_lint_flags_indivisible_fsdp(self, sharding_setup):
        from mxnet_tpu.parallel import sharding as shard

        mesh = sharding_setup[0]
        rules = shard.ShardingRules.fsdp(min_size=8)
        violations = sharding_placement.check_rules_coverage(
            rules, {"odd_weight": (7, 9)}, mesh)
        assert any("silently fully replicated" in v for v in violations)

    def test_lint_flags_fully_replicated_fsdp(self, sharding_setup):
        from mxnet_tpu.parallel import sharding as shard

        mesh = sharding_setup[0]
        rules = shard.ShardingRules.fsdp(min_size=10**9)
        violations = sharding_placement.check_rules_coverage(
            rules, {"w": (64, 16)}, mesh)
        assert any("partitioned NOTHING" in v for v in violations)

    def test_lint_detects_misplacement(self, sharding_setup):
        import jax
        from jax.sharding import NamedSharding
        from mxnet_tpu.parallel import PartitionSpec as P

        mesh, rules, step, eng, batch, shapes = sharding_setup
        name = next(n for n in step._train_vals
                    if step._param_sharding(n).spec != P())
        orig = step._train_vals[name]
        try:
            step._train_vals[name] = jax.device_put(
                jax.numpy.asarray(orig), NamedSharding(mesh, P()))
            violations = sharding_placement.check_step_placement(step)
            assert any(name in v for v in violations)
        finally:
            step._train_vals[name] = orig


# ================================================= lock-order self-tests
def _analyze(tmp_path, source):
    index, name = _write_module(tmp_path, source)
    return lock_order.analyze(index, [name])


class TestLockOrder:
    def test_detects_two_lock_deadlock_cycle(self, tmp_path):
        """Acceptance: a seeded two-lock cycle in serving-plane shape."""
        cycles, _, _ = _analyze(tmp_path, """
            import threading
            class Router:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._hb_lock = threading.Lock()
                def submit(self, r):
                    with self._lock:
                        with self._hb_lock:
                            return r
                def _health_pass(self):
                    with self._hb_lock:
                        with self._lock:
                            return 1
            """)
        assert cycles, "two-lock cycle not detected"
        locks = {f"{c}.{a}" for comp, _ in cycles for c, a in comp}
        assert {"Router._lock", "Router._hb_lock"} <= locks

    def test_detects_self_deadlock(self, tmp_path):
        cycles, _, _ = _analyze(tmp_path, """
            import threading
            class W:
                def __init__(self):
                    self._lock = threading.Lock()
                def poke(self):
                    with self._lock:
                        with self._lock:
                            pass
            """)
        assert any(len(comp) == 2 and comp[0] == comp[1]
                   for comp, _ in cycles)

    def test_detects_blocking_dispatch_under_lock(self, tmp_path):
        """Acceptance: a blocking engine dispatch fired under a lock."""
        _, blocking, _ = _analyze(tmp_path, """
            import threading
            class Batcher:
                def __init__(self):
                    self._lock = threading.Lock()
                def fire(self, reqs, fut):
                    with self._lock:
                        out = self._engine.decode_n(reqs)
                        return fut.result()
            """)
        msgs = [m for _, _, _, _, m, _ in blocking]
        assert any("decode_n" in m for m in msgs)
        assert any("result" in m for m in msgs)

    def test_detects_blocking_via_self_call(self, tmp_path):
        _, blocking, _ = _analyze(tmp_path, """
            import threading, time
            class B:
                def __init__(self):
                    self._lock = threading.Lock()
                def outer(self):
                    with self._lock:
                        self._inner()
                def _inner(self):
                    time.sleep(1.0)
            """)
        assert any("_inner" in m for _, _, _, _, m, _ in blocking)

    def test_cond_wait_on_held_condition_is_legal(self, tmp_path):
        _, blocking, _ = _analyze(tmp_path, """
            import threading
            class R:
                def __init__(self):
                    self._cond = threading.Condition()
                def wait_tokens(self):
                    with self._cond:
                        self._cond.wait(1.0)
            """)
        assert not blocking

    def test_detects_unsynchronized_shared_state(self, tmp_path):
        _, _, shared = _analyze(tmp_path, """
            import threading
            class B:
                def __init__(self):
                    self.stats = {}
                    self._thread = threading.Thread(target=self._run)
                def _run(self):
                    self.stats["n"] = 1
                def submit(self):
                    return sorted(self.stats)
            """)
        assert any(attr == "stats" for _, _, _, attr, _ in shared)

    def test_locked_writes_are_clean(self, tmp_path):
        cycles, blocking, shared = _analyze(tmp_path, """
            import threading
            class B:
                def __init__(self):
                    self.stats = {}
                    self._lock = threading.Lock()
                    self._thread = threading.Thread(target=self._run)
                def _run(self):
                    with self._lock:
                        self.stats["n"] = 1
                def submit(self):
                    with self._lock:
                        return sorted(self.stats)
            """)
        assert not cycles and not blocking and not shared

    def test_serving_plane_at_head_only_baselined_findings(self, ctx):
        findings = get_pass("lock-order").run(ctx)
        baseline = Baseline.load(BASELINE_PATH)
        fresh = [f for f in findings if baseline.reason(f) is None]
        assert not fresh, "\n".join(repr(f) for f in fresh)
        # the two grandfathered single-writer findings stay visible
        assert {f.key for f in findings} <= {
            "ContinuousBatcher._pending", "ContinuousBatcher._slots"}

    def test_cross_process_modules_in_scope(self):
        """The ISSUE-10 modules are part of the serving-plane set the
        pass walks at HEAD (the head test above then proves them
        finding-free)."""
        assert {"mxnet_tpu/serving/transport.py",
                "mxnet_tpu/serving/worker.py",
                "mxnet_tpu/serving/remote.py"} <= set(lock_order.MODULES)


class TestLockOrderTransport:
    """Seeded controls in the RPC client's thread shape: a socket READER
    thread routes responses while caller threads register calls — the
    call table is cross-domain state."""

    def test_unlocked_call_table_across_reader_flagged(self, tmp_path):
        """Positive: the reader thread rebuilds the call table while
        `call()` iterates it — the torn-table shape the real client must
        lock against."""
        _, _, shared = _analyze(tmp_path, """
            import threading
            class Client:
                def __init__(self):
                    self._calls = {}
                    self._reader = threading.Thread(
                        target=self._read_loop)
                def _read_loop(self):
                    self._calls = {}
                def call(self, verb):
                    return sorted(self._calls)
            """)
        assert any(attr == "_calls" for _, _, _, attr, _ in shared)

    def test_locked_call_table_clean(self, tmp_path):
        """Negative: every call-table touch under the client lock (the
        real `RpcClient` shape) is clean — including a send lock that is
        never nested with it."""
        cycles, blocking, shared = _analyze(tmp_path, """
            import threading
            class Client:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._send_lock = threading.Lock()
                    self._calls = {}
                    self._reader = threading.Thread(
                        target=self._read_loop)
                def _read_loop(self):
                    with self._lock:
                        self._calls = {}
                def call(self, verb):
                    with self._lock:
                        pending = list(self._calls.values())
                    with self._send_lock:
                        self._sock.sendall(verb)
                    return pending
            """)
        assert not cycles and not blocking and not shared


class TestLockOrderWorker:
    """Seeded controls in the worker's thread shape: per-request
    streamer threads relaying futures while handler/caller threads
    manage shared staging state."""

    def test_blocking_future_wait_under_lock_flagged(self, tmp_path):
        """Positive: a streamer waiting on a future's result while
        holding the worker lock couples every handler to decode
        latency — the hung-worker shape."""
        _, blocking, _ = _analyze(tmp_path, """
            import threading
            class Worker:
                def __init__(self):
                    self._lock = threading.Lock()
                def relay(self, fut):
                    with self._lock:
                        return fut.result()
            """)
        assert any("result" in m for _, _, _, _, m, _ in blocking)

    def test_locked_staging_with_waits_outside_clean(self, tmp_path):
        """Negative: the real worker shape — staged-swap state touched
        only under the lock, future waits outside any lock, streamer
        threads tracked under the lock — is clean."""
        cycles, blocking, shared = _analyze(tmp_path, """
            import threading
            class Worker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._staged = None
                    self._streamers = []
                    self._thread = threading.Thread(
                        target=self._stream_result)
                def _stream_result(self):
                    with self._lock:
                        self._streamers.append(1)
                    return self._fut.result()
                def handle_stage(self, arrays):
                    with self._lock:
                        self._staged = arrays
                def handle_swap(self):
                    with self._lock:
                        staged, self._staged = self._staged, None
                    return staged
            """)
        assert not cycles and not blocking and not shared


class TestLockOrderKvPush:
    """Seeded controls in the kv-push arrival path (ISSUE 11): transport
    reader threads stash pushed frames while submit handler/caller
    threads claim them — the stash is cross-domain state."""

    def test_unlocked_stash_across_reader_flagged(self, tmp_path):
        """Positive: the reader thread appends to the arrival order
        while callers iterate it unlocked — the torn-stash shape the
        real HandoffStash must lock against."""
        _, _, shared = _analyze(tmp_path, """
            import threading
            class Stash:
                def __init__(self):
                    self._frames = {}
                    self._order = []
                    self._reader = threading.Thread(
                        target=self._read_loop)
                def _read_loop(self):
                    self._order.append("h")
                def pop(self, handoff):
                    return sorted(self._order)
            """)
        assert any(attr == "_order" for _, _, _, attr, _ in shared)

    def test_locked_stash_clean(self, tmp_path):
        """Negative: the real HandoffStash shape — every frames/order
        touch under the stash lock, nothing blocking under it."""
        cycles, blocking, shared = _analyze(tmp_path, """
            import threading
            class Stash:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._frames = {}
                    self._order = []
                    self._reader = threading.Thread(
                        target=self._read_loop)
                def _read_loop(self):
                    with self._lock:
                        self._order.append("h")
                def pop(self, handoff):
                    with self._lock:
                        order = sorted(self._order)
                        return self._frames.pop(handoff, None)
            """)
        assert not cycles and not blocking and not shared


class TestLockOrderScaler:
    """Seeded controls in the fleet-scaler's thread shape (ISSUE 11): a
    supervisor loop thread mutating decision state that public ``step``
    callers also touch."""

    def test_unlocked_decision_state_flagged(self, tmp_path):
        """Positive: the loop thread appends action records while
        callers iterate them unlocked."""
        _, _, shared = _analyze(tmp_path, """
            import threading
            class Scaler:
                def __init__(self):
                    self.actions = []
                    self._thread = threading.Thread(target=self._run)
                def _run(self):
                    self.actions.append("up")
                def history(self):
                    return sorted(self.actions)
            """)
        assert any(attr == "actions" for _, _, _, attr, _ in shared)

    def test_decide_under_lock_act_outside_clean(self, tmp_path):
        """Negative: the real FleetScaler shape — decisions (and every
        state write) under the scaler lock via a ``*_locked`` helper,
        the potentially-blocking spawn/retire callables OUTSIDE it."""
        cycles, blocking, shared = _analyze(tmp_path, """
            import threading
            class Scaler:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.actions = []
                    self._hot = 0
                    self._thread = threading.Thread(target=self._run)
                def _run(self):
                    self.step()
                def step(self):
                    with self._lock:
                        action = self._decide_locked()
                    if action is not None:
                        self._spawn()
                    return action
                def _decide_locked(self):
                    self._hot += 1
                    if self._hot >= 2:
                        self.actions.append("up")
                        return "up"
                    return None
            """)
        assert not cycles and not blocking and not shared

    def test_disagg_modules_in_scope(self):
        """The ISSUE-11 modules are part of the serving-plane set the
        lock-order pass walks at HEAD (the head test above then proves
        them finding-free)."""
        assert {"mxnet_tpu/serving/disagg.py",
                "tools/launch.py"} <= set(lock_order.MODULES)


# ================================================== donation self-tests
class TestDonation:
    def test_real_modules_satisfy_contract(self, ctx):
        for path, req in ((donation.STEP_PY, donation.REQUIRED_STEP),
                          (donation.INFER_PY, donation.REQUIRED_INFER)):
            out = donation.check_contract(ctx.ast.module(path), req, path)
            assert not out, out

    def test_contract_catches_missing_donation(self, tmp_path):
        index, name = _write_module(tmp_path, """
            import jax
            class TrainStep:
                def _build(self):
                    def step(train_vals, opt_state, batch, key, t):
                        return train_vals, opt_state, key, t
                    return jax.jit(step, donate_argnums=(0,))
            """)
        out = donation.check_contract(
            index.module(name), donation.REQUIRED_STEP, name)
        assert any("opt_state" in m for _, _, m in out)

    def test_contract_catches_forbidden_donation(self, tmp_path):
        index, name = _write_module(tmp_path, """
            import jax
            class TrainStep:
                def _build(self):
                    def step(train_vals, opt_state, batch, key, t):
                        return train_vals, opt_state, key, t
                    return jax.jit(step, donate_argnums=(0, 1, 2, 3, 4))
            """)
        out = donation.check_contract(
            index.module(name), donation.REQUIRED_STEP, name)
        assert any("batch" in m for _, _, m in out)

    def test_catches_host_read_of_donated_pool_after_decode_iter(
            self, tmp_path):
        """Acceptance: a seeded host read of a donated pool after
        decode_iter."""
        index, name = _write_module(tmp_path, """
            class Batcher:
                def _dispatch(self, live):
                    buf, self._state = self._engine.decode_iter(
                        self._state, self.tables, live)
                    return buf
                def _peek(self):
                    out = self._engine.decode_iter(self._state, self.t, 1)
                    pool = self._state["k_pools"]
                    return out, pool
            """)
        out = donation.check_use_after_donate(index.module(name))
        assert any("use-after" in key for _, key, _ in out)
        # the rebind-in-same-statement pattern (_dispatch) is NOT flagged
        assert not any("_dispatch" in key for _, key, _ in out)

    def test_catches_lost_carry(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class Batcher:
                def _fire(self, live):
                    buf = self._engine.decode_iter(self._state, live)
                    return buf
            """)
        out = donation.check_use_after_donate(index.module(name))
        assert any("lost" in key for _, key, _ in out)

    def test_serving_scheduler_clean_at_head(self, ctx):
        out = donation.check_use_after_donate(
            ctx.ast.module(donation.BATCHER_PY))
        assert not out, out

    def test_real_programs_donations_consumed_and_aliasable(self, ctx):
        msgs = donation.run_jaxpr_checks(ctx.programs)
        assert not msgs, "\n".join(msgs)


# ========================================== recompile-hazard self-tests
class TestRecompileHazard:
    def test_real_modules_clean(self, ctx):
        for path in (recompile.STEP_PY, recompile.INFER_PY):
            mod = ctx.ast.module(path)
            assert not recompile.check_cfg_hygiene(mod)
            assert not recompile.check_traced_closures(
                mod, recompile.TRACED_BUILDERS[path])
            assert not recompile.check_guard_accounting(
                mod, recompile.GUARDED_DISPATCHES[path])

    def test_catches_float_in_cfg_key(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class InferStep:
                def _decode_cfg(self, max_new, method, temperature):
                    return int(max_new), str(method), float(temperature)
            """)
        out = recompile.check_cfg_hygiene(index.module(name))
        assert any("float" in key for _, key, _ in out)

    def test_catches_shape_branch_in_traced_closure(self, tmp_path):
        """Acceptance: a seeded recompile hazard."""
        index, name = _write_module(tmp_path, """
            class InferStep:
                def _get_decode_fn(self, cfg):
                    def decode(values, state, tokens):
                        if len(tokens) > 4:
                            return state
                        return values
                    return decode
            """)
        out = recompile.check_traced_closures(
            index.module(name), ("_get_decode_fn",))
        assert any("shape-branch" in key for _, key, _ in out)

    def test_catches_host_entropy_in_traced_closure(self, tmp_path):
        index, name = _write_module(tmp_path, """
            import time
            class InferStep:
                def _get_decode_fn(self, cfg):
                    def decode(values, tokens):
                        return values * time.time()
                    return decode
            """)
        out = recompile.check_traced_closures(
            index.module(name), ("_get_decode_fn",))
        assert any("host-entropy" in key for _, key, _ in out)

    def test_catches_unaccounted_dispatch(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class InferStep:
                def decode_n(self, src):
                    fn = self._get_decode_fn(4)
                    return fn(self._values, src)
            """)
        out = recompile.check_guard_accounting(
            index.module(name), ("decode_n",))
        assert any("unaccounted" in key for _, key, _ in out)

    def test_guard_crosscheck_on_real_engine(self, ctx):
        msgs = recompile.run_guard_crosscheck(ctx.programs)
        assert not msgs, "\n".join(msgs)


# ================================= prefix-caching pass extensions (ISSUE 13)
class TestPrefixCachingPassScope:
    """The prefix-caching surface (``serving/prefix.py``, the
    ``prefill_suffix_paged`` replay dispatch, the ``_get_suffix_fn``
    builder) sits inside every relevant pass's scope — coverage
    assertions plus seeded positive/negative controls. The at-HEAD
    cleanliness of the real modules rides the existing full-suite and
    lock-order head tests."""

    def test_new_surface_is_in_scope(self):
        assert "mxnet_tpu/serving/prefix.py" in lock_order.MODULES
        covered = {(os.path.basename(p), cls): set(funcs)
                   for p, cls, funcs in no_sync.TARGETS}
        assert "prefill_suffix_paged" in covered[("infer.py", "InferStep")]
        assert "prefill_suffix_paged" in donation.DONATING_CALLS
        assert "prefill_suffix_paged" in \
            recompile.GUARDED_DISPATCHES[recompile.INFER_PY]
        assert "_get_suffix_fn" in \
            recompile.TRACED_BUILDERS[recompile.INFER_PY]

    def test_unlocked_trie_across_health_reader_flagged(self, tmp_path):
        """Positive: trie state shared between the scheduler and a
        health-verb reader thread without the cache lock."""
        _, _, shared = _analyze(tmp_path, """
            import threading
            class PrefixCache:
                def __init__(self):
                    self._roots = {}
                    self._reader = threading.Thread(target=self._health)
                def _health(self):
                    self._roots = {}
                def insert(self, key):
                    return sorted(self._roots)
            """)
        assert any(attr == "_roots" for _, _, _, attr, _ in shared)

    def test_locked_trie_clean(self, tmp_path):
        """Negative: every trie touch under the cache lock (the real
        ``PrefixCache`` shape) is clean."""
        cycles, blocking, shared = _analyze(tmp_path, """
            import threading
            class PrefixCache:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._roots = {}
                    self._reader = threading.Thread(target=self._health)
                def _health(self):
                    with self._lock:
                        self._roots = {}
                def insert(self, key):
                    with self._lock:
                        return sorted(self._roots)
            """)
        assert not cycles and not blocking and not shared

    def test_sync_in_suffix_replay_flagged(self, tmp_path):
        bad = tmp_path / "infer_suffix_bad.py"
        bad.write_text(
            "class InferStep:\n"
            "    def prefill_suffix_paged(self, state, rows):\n"
            "        buf, state = self._fn(state, rows)\n"
            "        return buf.asnumpy(), state\n"
        )
        violations = no_sync.find_violations(
            str(bad), "InferStep", ("prefill_suffix_paged",))
        assert len(violations) == 1
        assert "asnumpy" in violations[0][1]

    def test_suffix_replay_lost_carry_flagged(self, tmp_path):
        """Positive: dropping the donated state carry of the suffix
        replay is a use-after-donate bug."""
        index, name = _write_module(tmp_path, """
            class Batcher:
                def _replay(self, rows):
                    buf = self._engine.prefill_suffix_paged(
                        self._state, rows)
                    return buf
            """)
        out = donation.check_use_after_donate(index.module(name))
        assert any("lost" in key for _, key, _ in out)

    def test_unaccounted_suffix_dispatch_flagged(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class InferStep:
                def prefill_suffix_paged(self, state, rows):
                    fn = self._get_suffix_fn(8)
                    return fn(self._values, state, rows)
            """)
        out = recompile.check_guard_accounting(
            index.module(name), ("prefill_suffix_paged",))
        assert any("unaccounted" in key for _, key, _ in out)


# =============================== speculative-decoding pass extensions (ISSUE 14)
class TestSpeculativePassScope:
    """The speculative surface (``spec_draft``/``spec_verify`` dispatches,
    their traced builders, the batcher's spec round) sits inside every
    relevant pass's scope — coverage assertions plus seeded positive/
    negative controls. At-HEAD cleanliness of the real modules rides the
    existing full-suite and per-pass head tests."""

    def test_new_surface_is_in_scope(self):
        covered = {(os.path.basename(p), cls): set(funcs)
                   for p, cls, funcs in no_sync.TARGETS}
        infer = covered[("infer.py", "InferStep")]
        assert {"spec_draft", "spec_verify"} <= infer
        assert {"spec_draft", "spec_verify"} <= \
            set(donation.DONATING_CALLS)
        assert {"spec_draft", "spec_verify"} <= \
            set(recompile.GUARDED_DISPATCHES[recompile.INFER_PY])
        assert {"_get_spec_draft_fn", "_get_spec_verify_fn"} <= \
            set(recompile.TRACED_BUILDERS[recompile.INFER_PY])
        assert {"spec_draft", "spec_verify"} <= lock_order.DISPATCH_ATTRS

    def test_sync_in_spec_round_flagged(self, tmp_path):
        """Positive: host syncs inside the draft/verify dispatches."""
        bad = tmp_path / "infer_spec_bad.py"
        bad.write_text(
            "class InferStep:\n"
            "    def spec_draft(self, dstate, tables, tokens):\n"
            "        buf, dstate = self._fn(dstate, tables, tokens)\n"
            "        return buf.asnumpy(), dstate\n"
            "    def spec_verify(self, state, tables, drafts):\n"
            "        buf, state = self._fn(state, tables, drafts)\n"
            "        return int(buf[0, -1]), state\n"
        )
        violations = no_sync.find_violations(
            str(bad), "InferStep", ("spec_draft", "spec_verify"))
        assert len(violations) == 2
        assert any("asnumpy" in m for _, m in violations)
        assert any("int" in m for _, m in violations)

    def test_clean_spec_dispatch_passes(self, tmp_path):
        """Negative: the real shape — dispatch returns device buffers,
        carry rebinds in the same statement — is sync-free."""
        good = tmp_path / "infer_spec_good.py"
        good.write_text(
            "class InferStep:\n"
            "    def spec_verify(self, state, tables, drafts):\n"
            "        fn = self._get_spec_verify_fn(4)\n"
            "        self.compile_guard.observe(('spec_verify', 4))\n"
            "        buf, state = fn(self._values, state, tables, drafts)\n"
            "        return buf, state\n"
        )
        assert not no_sync.find_violations(
            str(good), "InferStep", ("spec_verify",))

    def test_spec_lost_carry_flagged(self, tmp_path):
        """Positive: dropping the donated draft-state carry of
        spec_draft is a use-after-donate bug."""
        index, name = _write_module(tmp_path, """
            class Batcher:
                def _spec_round(self, tokens):
                    dbuf = self._engine.spec_draft(
                        self._dstate, self.tables, tokens)
                    return dbuf
            """)
        out = donation.check_use_after_donate(index.module(name))
        assert any("lost" in key for _, key, _ in out)

    def test_unaccounted_spec_dispatch_flagged(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class InferStep:
                def spec_verify(self, state, tables, drafts):
                    fn = self._get_spec_verify_fn(4)
                    return fn(self._values, state, tables, drafts)
            """)
        out = recompile.check_guard_accounting(
            index.module(name), ("spec_verify",))
        assert any("unaccounted" in key for _, key, _ in out)

    def test_shape_branch_in_spec_builder_flagged(self, tmp_path):
        """Positive: a data-dependent shape branch inside the traced
        verify closure is a per-round recompile."""
        index, name = _write_module(tmp_path, """
            class InferStep:
                def _get_spec_verify_fn(self, k):
                    def verify(values, state, drafts):
                        if len(drafts) > 2:
                            return state
                        return values
                    return verify
            """)
        out = recompile.check_traced_closures(
            index.module(name), ("_get_spec_verify_fn",))
        assert any("shape-branch" in key for _, key, _ in out)


# ===================================== collective-placement self-tests
class TestCollectivePlacement:
    def test_decode_programs_dispatch_no_collectives(self, ctx):
        """Acceptance: no psum/all_gather in the default decode path."""
        msgs = collectives.check_decode_collectives(ctx.programs)
        assert not msgs, "\n".join(msgs)

    def test_collective_primitives_are_detectable(self):
        import jax

        jaxpr = jax.make_jaxpr(
            lambda x: jax.lax.psum(x, "i"), axis_env=[("i", 2)])(1.0)
        hit = jaxpr_driver.primitive_names(jaxpr) & \
            collectives.COLLECTIVE_PRIMITIVES
        assert "psum" in hit

    def test_host_allreduce_guards_present_at_head(self, ctx):
        out = collectives.check_host_allreduce_guard(ctx.ast)
        assert not out, out

    def test_catches_unguarded_host_allreduce(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class Trainer:
                def _allreduce_grads(self):
                    for k in self._grad_keys:
                        self._kvstore.push(k, self._grads[k])
                        self._kvstore.pull(k, self._grads[k])
            """)
        out = collectives.check_host_allreduce_guard(
            index, sites=((name, "Trainer", "_allreduce_grads",
                           "return-guard"),))
        assert any("unguarded" in key for _, key, _ in out)


# ============================================= env-vars / telemetry-names
class TestConsistencyPasses:
    def test_env_vars_consistent_at_head(self, ctx):
        findings = get_pass("env-vars").run(ctx)
        assert not findings, "\n".join(repr(f) for f in findings)

    def test_detects_undocumented_and_dead_vars(self, tmp_path):
        (tmp_path / "mxnet_tpu").mkdir()
        (tmp_path / "mxnet_tpu" / "mod.py").write_text(
            "import os\n"
            "A = os.environ.get('MXTPU_SECRET_KNOB', '1')\n")
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "ENV_VARS.md").write_text(
            "| `MXTPU_GHOST_KNOB` | `1` | long gone |\n")
        index = ast_driver.AstIndex(str(tmp_path))
        code = env_vars.collect_code_vars(index)
        doc = env_vars.collect_doc_vars(str(tmp_path))
        assert "MXTPU_SECRET_KNOB" in code
        assert not env_vars._doc_covers("MXTPU_SECRET_KNOB", doc)
        assert not env_vars._code_covers("MXTPU_GHOST_KNOB", set(code))

    def test_prefix_rows_cover_prefix_uses(self):
        doc = {"MXTPU_FAULT_": 1}
        assert env_vars._doc_covers("MXTPU_FAULT_BATCHER_HANG", doc)
        assert env_vars._code_covers("MXTPU_FAULT_",
                                     {"MXTPU_FAULT_", "MXTPU_X"})

    def test_telemetry_names_consistent_at_head(self, ctx):
        findings = get_pass("telemetry-names").run(ctx)
        assert not findings, "\n".join(repr(f) for f in findings)

    def test_report_tool_declares_every_emitted_family(self, ctx):
        metrics, spans = telemetry_names.collect_emissions(ctx.ast)
        known_m, known_s, _ = telemetry_names.declared_families(ctx.ast)
        assert set(metrics) <= known_m
        assert set(spans) <= known_s


# ==================================== interprocedural layer (ISSUE 15)
class TestCallGraph:
    """The shared layer under the three new passes: resolution,
    exception summaries, thread entries."""

    def test_resolves_self_attr_and_module_calls(self, tmp_path):
        index, name = _write_module(tmp_path, """
            def helper():
                return 1

            class A:
                def top(self):
                    self.mid()
                    helper()

                def mid(self):
                    pass
            """)
        g = callgraph.ProjectGraph(index, (name,))
        tops = dict(g.nodes[("A", "top")].calls)
        callees = {c for c in tops.values() if c is not None}
        assert ("A", "mid") in callees
        assert (name, "helper") in callees
        assert [k for k, _ in g.callers_of(("A", "mid"))] == [("A", "top")]

    def test_may_raise_propagates_and_broad_catch_stops_it(
            self, tmp_path):
        index, name = _write_module(tmp_path, """
            class A:
                def deep(self):
                    raise ValueError("boom")

                def mid(self):
                    self.deep()

                def caught(self):
                    try:
                        self.deep()
                    except Exception:
                        pass

                def rethrown(self):
                    try:
                        self.deep()
                    except Exception:
                        raise
            """)
        g = callgraph.ProjectGraph(index, (name,))
        assert g.may_raise(("A", "deep"))
        assert g.may_raise(("A", "mid"))      # transitively
        assert not g.may_raise(("A", "caught"))
        assert g.may_raise(("A", "rethrown"))  # handler re-raises

    def test_typed_attrs_resolve_cross_class(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class Pool:
                def free(self):
                    raise RuntimeError("x")

            class User:
                def __init__(self):
                    self.pool = Pool()

                def use(self):
                    self.pool.free()
            """)
        g = callgraph.ProjectGraph(index, (name,))
        calls = dict(g.nodes[("User", "use")].calls)
        assert ("Pool", "free") in calls.values()
        assert g.may_raise(("User", "use"))

    def test_thread_entries_found(self, tmp_path):
        index, name = _write_module(tmp_path, """
            import threading

            class W:
                def start(self):
                    t = threading.Thread(target=self._run, daemon=True)
                    t.start()

                def _run(self):
                    pass
            """)
        g = callgraph.ProjectGraph(index, (name,))
        assert ("W", "_run") in g.thread_entries


class TestResourceLeakPass:
    """Seeded positive/negative controls (ISSUE 15 pattern: leaked page
    on raise vs balanced release), plus the head gate."""

    LEAKY = """
        class Worker:
            def __init__(self):
                self.pool = PagePool(16)

            def grab(self):
                page = self.pool.alloc(1)
                self.validate(page)
                self.pool.release(page)

            def validate(self, page):
                if page is None:
                    raise ValueError("bad page")
        """

    def test_detects_page_leak_on_exception_edge(self, tmp_path):
        index, name = _write_module(tmp_path, self.LEAKY)
        leaks, futures, stashes = resource_leak.analyze(
            index, rel_paths=(name,))
        assert len(leaks) == 1
        path, line, where, kind, recv, msg = leaks[0]
        assert (path, kind, recv) == (name, "pool-page", "pool")
        assert "Worker.grab" in where
        # stable fingerprint: a second run reproduces it exactly
        assert resource_leak.analyze(index, rel_paths=(name,))[0] == leaks

    def test_balanced_release_is_clean(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class Worker:
                def __init__(self):
                    self.pool = PagePool(16)

                def grab(self):
                    page = self.pool.alloc(1)
                    try:
                        self.validate(page)
                    finally:
                        self.pool.release(page)

                def validate(self, page):
                    if page is None:
                        raise ValueError("bad page")
            """)
        leaks, futures, stashes = resource_leak.analyze(
            index, rel_paths=(name,))
        assert leaks == [] and futures == [] and stashes == []

    def test_broad_handler_in_caller_discharges(self, tmp_path):
        """The _step_once shape: a broad no-re-raise handler anywhere up
        the call chain owns the cleanup (the poison contract)."""
        index, name = _write_module(tmp_path, self.LEAKY + """
        class Sched:
            def __init__(self):
                self.w = Worker()

            def step(self):
                try:
                    self.w.grab()
                except Exception as e:
                    self.poison(e)

            def poison(self, e):
                pass
        """)
        leaks, _f, _s = resource_leak.analyze(index, rel_paths=(name,))
        # Worker.grab is no longer a root (Sched.step calls it and
        # catches): nothing reaches an uncaught root
        assert leaks == []

    def test_detects_unfailed_future_and_failed_is_clean(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class Bad:
                def kick(self, p):
                    fut = GenerationResult()
                    self.check(p)
                    return fut

                def check(self, p):
                    if not p:
                        raise ValueError("empty")

            class Good:
                def kick(self, p):
                    fut = GenerationResult()
                    try:
                        self.check(p)
                    except Exception as e:
                        fut._fail(e)
                        raise
                    return fut

                def check(self, p):
                    if not p:
                        raise ValueError("empty")
            """)
        _l, futures, _s = resource_leak.analyze(index, rel_paths=(name,))
        assert len(futures) == 1
        assert "Bad.kick" in futures[0][2]

    def test_detects_clockless_stash(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class FrameStash:
                def put(self, k, v):
                    self.d[k] = v

                def pop(self, k):
                    return self.d.pop(k, None)
            """)
        _l, _f, stashes = resource_leak.analyze(index, rel_paths=(name,))
        assert len(stashes) == 1 and "FrameStash" in stashes[0][2]

    def test_expiring_stash_is_clean(self, tmp_path):
        index, name = _write_module(tmp_path, """
            import time

            class FrameStash:
                def put(self, k, v):
                    now = time.monotonic()
                    self.d[k] = (v, now)

                def pop(self, k):
                    self.expire(time.monotonic())
                    return self.d.pop(k, None)

                def expire(self, now):
                    pass
            """)
        _l, _f, stashes = resource_leak.analyze(index, rel_paths=(name,))
        assert stashes == []

    def test_serving_plane_clean_at_head(self, ctx):
        findings = get_pass("resource-leak").run(ctx)
        assert not findings, "\n".join(repr(f) for f in findings)


class TestRpcProtocolPass:
    """Seeded controls: orphan verb + reply-key drift in BOTH directions
    vs a clean verb pair, plus the head gate."""

    BAD = """
        class RpcServer:
            def __init__(self, handlers):
                self.handlers = handlers

        class Server:
            def start(self):
                self.srv = RpcServer({"ping": self._handle_ping})

            def _handle_ping(self, msg, respond):
                respond(pong=True, extra=1)

        class Client:
            def check(self):
                out = self.conn.call("ping", {}, timeout_s=1.0)
                return out["latency"]

            def poke(self):
                self.conn.call("pong", {})
        """

    def test_detects_orphan_drift_and_timeout(self, tmp_path):
        index, name = _write_module(tmp_path, self.BAD)
        facts = rpc_protocol.analyze(index, server_paths=(name,),
                                     client_paths=(name,))
        assert set(facts["verbs"]) == {"ping"}
        assert [(v, w) for v, _p, _ln, w in facts["orphans"]] == \
            [("pong", "Client.poke")]
        # drift, read direction: caller reads a key never responded
        assert [(v, k) for v, k, _p, _ln in facts["missing_reply"]] == \
            [("ping", "latency")]
        # drift, respond direction: keys sent that nobody reads
        assert facts["unread"] == {"ping": ["extra", "pong"]}
        # the orphan send also carries no timeout
        assert [(v, w) for v, _p, _ln, w in
                facts["missing_timeout"]] == [("pong", "Client.poke")]
        # no fault point anywhere reaches the verb
        assert facts["unreachable_fault"] == ["ping"]
        # stability
        again = rpc_protocol.analyze(index, server_paths=(name,),
                                     client_paths=(name,))
        assert again["orphans"] == facts["orphans"]
        assert again["missing_reply"] == facts["missing_reply"]

    def test_clean_pair_is_clean(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class RpcServer:
                def __init__(self, handlers):
                    self.handlers = handlers

            class Server:
                def start(self):
                    _faults.fire("transport.send")
                    _faults.fire("transport.recv")
                    self.srv = RpcServer({"ping": self._handle_ping})

                def _handle_ping(self, msg, respond):
                    respond(pong=True)

            class Client:
                def check(self):
                    out = self.conn.call("ping", {}, timeout_s=1.0)
                    return out["pong"]
            """)
        facts = rpc_protocol.analyze(index, server_paths=(name,),
                                     client_paths=(name,))
        assert facts["orphans"] == [] and facts["dead"] == []
        assert facts["missing_reply"] == [] and facts["unread"] == {}
        assert facts["missing_timeout"] == []
        assert facts["unreachable_fault"] == []

    def test_dead_verb_needs_a_caller_somewhere(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class RpcServer:
                def __init__(self, handlers):
                    self.handlers = handlers

            class Server:
                def start(self):
                    self.srv = RpcServer({"ghost": self._handle_ghost})

                def _handle_ghost(self, msg, respond):
                    respond(ok=True)
            """)
        facts = rpc_protocol.analyze(index, server_paths=(name,),
                                     client_paths=(name,))
        assert facts["dead"] == ["ghost"]
        # a test-suite send keeps it alive (the liveness scan)
        (tmp_path / "test_x.py").write_text(
            "def test_g(c):\n    c.call('ghost', {})\n")
        facts = rpc_protocol.analyze(index, server_paths=(name,),
                                     client_paths=(name,),
                                     liveness_paths=("test_x.py",))
        assert facts["dead"] == []

    def test_worker_protocol_clean_at_head(self, ctx):
        findings = get_pass("rpc-protocol").run(ctx)
        assert not findings, "\n".join(repr(f) for f in findings)

    def test_head_verb_table_extracted(self, ctx):
        facts = rpc_protocol.analyze(ctx.ast)
        assert {"ping", "health", "submit", "prefill", "kv_push",
                "stage", "swap", "drain"} <= set(facts["verbs"])


class TestSwapBarrierPass:
    """Seeded controls: flip-before-stage reorder + stale engine set +
    unguarded flip vs the correct two-phase barrier, plus the head
    gate."""

    def test_detects_flip_before_stage(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class Watcher:
                def poll_once_locked(self):
                    engines = list(self.engines)
                    for eng in engines:
                        eng.swap_params(staged=self.staged, version="v")
                    staged = [e.stage_params({}) for e in engines]
            """)
        got = swap_barrier.analyze(index, rel_paths=(name,))
        assert [r for r, *_ in got] == ["flip-before-stage"]
        assert swap_barrier.analyze(index, rel_paths=(name,)) == got

    def test_detects_stale_engine_set(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class Watcher:
                def poll_once_locked(self):
                    staged = [e.stage_params({}) for e in self.local()]
                    for eng in self.engines():
                        eng.swap_params(staged=staged, version="v")
            """)
        got = swap_barrier.analyze(index, rel_paths=(name,))
        assert "stale-engine-set" in [r for r, *_ in got]

    def test_detects_stage_fallthrough_and_unguarded_flip(
            self, tmp_path):
        index, name = _write_module(tmp_path, """
            class Watcher:
                def poll_once_locked(self):
                    engines = list(self.engines)
                    try:
                        staged = [e.stage_params({}) for e in engines]
                    except Exception:
                        staged = []
                    for eng, v in zip(engines, staged):
                        eng.swap_params(staged=v, version="x")

            class Handle:
                def flip(self, version):
                    self.eng.swap_staged(version)
            """)
        rules = [r for r, *_ in
                 swap_barrier.analyze(index, rel_paths=(name,))]
        assert "stage-fallthrough" in rules
        assert "unguarded-flip" in rules

    def test_correct_barrier_is_clean(self, tmp_path):
        index, name = _write_module(tmp_path, """
            class GoodWatcher:
                def poll_once_locked(self):
                    engines = list(self.engines)
                    staged = [e.stage_params({}) for e in engines]
                    for eng, vals in zip(engines, staged):
                        eng.swap_params(staged=vals, version="v")

            class GoodHandle:
                def swap_staged(self, version):
                    self.eng.swap_staged(version)

                def handle_swap(self, msg):
                    staged = self.staged
                    if staged is None:
                        raise ValueError("no staged weights")
                    self.eng.swap_params(staged=staged, version=msg)
            """)
        assert swap_barrier.analyze(index, rel_paths=(name,)) == []

    def test_watcher_clean_at_head(self, ctx):
        findings = get_pass("swap-barrier").run(ctx)
        assert not findings, "\n".join(repr(f) for f in findings)


# ===================================== regression tests for fixed races
class TestServingRaceFixes:
    def test_admission_control_races_scheduler_safely(self, ctx):
        """PR fix: ContinuousBatcher.stats/_recent_waits are written by
        the scheduler thread and read by submit-side admission control;
        unsynchronized, sorted() over the live deque raises 'deque
        mutated during iteration'. Hammer admission from several caller
        threads while the scheduler streams decodes."""
        from mxnet_tpu.serving.batcher import ContinuousBatcher

        eng = ctx.programs.infer_engine
        b = ContinuousBatcher(eng, bucket_keys=(8,), slots=2,
                              max_new_tokens=4,
                              admit_max_wait_ms=10_000.0)
        errors = []
        rng = np.random.RandomState(0)
        prompts = [rng.randint(3, 60, (5,)).astype(np.int32)
                   for _ in range(24)]

        def feed(chunk):
            try:
                futs = [b.submit(p) for p in chunk]
                for f in futs:
                    try:
                        f.result(timeout=120)
                    except Exception:  # noqa: BLE001 - Backpressure ok
                        pass
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=feed, args=(prompts[i::4],))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        b.stop()
        assert not errors, errors
        with b._stats_lock:
            assert b.stats["retired"] + b.stats["rejected"] >= 1

    def test_watcher_concurrent_polls_swap_once(self, ctx, tmp_path):
        """PR fix: poll_once is serialized — N concurrent polls of one
        newly committed checkpoint produce exactly ONE swap (previously
        both threads could pass the token check and double-stage)."""
        from mxnet_tpu import checkpoint_sharded as cs
        from mxnet_tpu.serving import CheckpointWatcher

        eng = ctx.programs.infer_engine
        cs.save_sharded(
            str(tmp_path),
            {n: p._data.data
             for n, p in eng._net.collect_params().items()})
        swaps = []
        w = CheckpointWatcher(eng, str(tmp_path), start=False,
                              on_swap=lambda v, p: swaps.append(v))
        results = []
        barrier = threading.Barrier(4)

        def poll():
            barrier.wait()
            results.append(w.poll_once())

        threads = [threading.Thread(target=poll) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert sum(1 for r in results if r is not None) == 1
        assert len(swaps) == 1

    def test_router_replica_list_reads_are_snapshots(self, ctx):
        """PR fix: Router._replicas iteration sites read a lock-held
        snapshot (the lock-order pass verifies statically; this pins
        the helper's behavior)."""
        findings = get_pass("lock-order").run(ctx)
        assert not any(f.key == "Router._replicas" for f in findings)
