"""sharding-placement pass: declared shardings must actually hold.

The three checks of PR 6's sharding lint, on the pass framework
(``python tools/mxlint.py --passes sharding-placement``). GSPMD fails soft: an array placed
with the wrong (or no) sharding still computes — XLA inserts resharding
copies and the "FSDP" run silently trains fully replicated, OOMing at
exactly the scale sharding was meant to unlock.

1. **Declared == placed** for every param/opt-state leaf entering the
   jitted TrainStep/InferStep (live ``Array.sharding`` comparison).
2. **Placements survive the step** — after one real (donated) dispatch
   the updated state still carries the declared shardings.
3. **No silent replication fallback** — every pattern rule matches,
   fsdp shards everything shardable, something is partitioned at all.
"""

from __future__ import annotations

import os

from ..core import AnalysisPass, register

SHARDING_PY = "mxnet_tpu/parallel/sharding.py"


# ----------------------------------------------------------------- checks
def declared_shardings(step) -> dict:
    """name -> declared NamedSharding for every param of a built step."""
    if step._param_sharding is None:
        return {}
    out = {}
    for name, v in step._values.items():
        if hasattr(step._param_sharding, "__call__"):
            try:
                out[name] = step._param_sharding(name)
            except TypeError:
                # InferStep's placement closure takes (name, shape)
                out[name] = step._param_sharding(name, v.shape)
    return out


def _matches(got, want, ndim) -> bool:
    """Sharding equivalence (``is_equivalent_to`` ignores PartitionSpec
    canonicalization like trailing-None stripping)."""
    if got is None:
        return False
    try:
        return bool(got.is_equivalent_to(want, ndim))
    except Exception:  # noqa: BLE001 - cross-type comparisons
        return got == want


def check_step_placement(step, label="TrainStep") -> list:
    """Check (1): live param/opt-state arrays carry the declared
    shardings."""
    violations = []
    want = declared_shardings(step)
    if not want:
        return [f"{label}: no param shardings declared (mesh missing?)"]
    for name, v in step._values.items():
        got = getattr(v, "sharding", None)
        if not _matches(got, want[name], v.ndim):
            violations.append(
                f"{label}: param {name} placed with {got}, declared "
                f"{want[name].spec}")
    for name, st in getattr(step, "_opt_state", {}).items():
        for i, s in enumerate(st):
            got = getattr(s, "sharding", None)
            if not _matches(got, want[name], s.ndim):
                violations.append(
                    f"{label}: opt state {name}[{i}] placed with {got}, "
                    f"declared {want[name].spec} (moments must follow "
                    "their param — the ZeRO contract)")
    return violations


def check_post_step_placement(step, batch) -> list:
    """Check (2): run one real dispatch; the returned (donated) state
    must still carry the declared shardings."""
    step(*batch)
    violations = []
    want = declared_shardings(step)
    for name, v in step._train_vals.items():
        if not _matches(v.sharding, want[name], v.ndim):
            violations.append(
                f"TrainStep: param {name} came back from the jitted step "
                f"as {v.sharding.spec if hasattr(v.sharding, 'spec') else v.sharding}, "
                f"declared {want[name].spec} — out_shardings degraded")
    for name, st in step._opt_state.items():
        for i, s in enumerate(st):
            if not _matches(s.sharding, want[name], s.ndim):
                violations.append(
                    f"TrainStep: opt state {name}[{i}] degraded to "
                    f"{s.sharding} after one step")
    return violations


def check_rules_coverage(rules, shapes: dict, mesh) -> list:
    """Check (3): no rule silently falls back to full replication."""
    violations = []
    matched = {pat: 0 for pat, _ in rules.rules}
    partitioned = 0
    from jax.sharding import PartitionSpec

    for name, shape in shapes.items():
        spec, reason = rules.param_explain(name, shape, mesh)
        if reason.startswith("rule:"):
            matched[reason[5:]] += 1
        if reason == "replicated:indivisible":
            violations.append(
                f"rules: param {name} {shape} is large enough to shard "
                f"but NO dim divides the '{rules.fsdp_axis}' axis "
                f"(size {mesh.shape.get(rules.fsdp_axis)}) — silently "
                "fully replicated")
        if spec != PartitionSpec():
            partitioned += 1
    for pat, n in matched.items():
        if n == 0:
            violations.append(
                f"rules: pattern {pat!r} matched NO parameter — the "
                "placement it declares is silently inert")
    if rules.params == "fsdp" and partitioned == 0:
        violations.append(
            "rules: fsdp policy partitioned NOTHING (axis missing from "
            "the mesh, axis size 1, or every param under fsdp_min_size="
            f"{rules.fsdp_min_size}) — the run is fully replicated")
    return violations


# ------------------------------------------------------------ default rig
def ensure_devices():
    """Standalone runs on a bare CPU get a simulated 4-device platform
    (the tests' conftest already forces 8)."""
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")


def build_default_setup():
    """A small FSDP-sharded TrainStep + InferStep on a 4-device mesh:
    the placement surface the lint walks."""
    import numpy as np

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, optimizer as opt
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import TrainStep, InferStep
    from mxnet_tpu.parallel import sharding as shard

    mesh = shard.make_global_mesh({"data": 4},
                                  devices=jax.devices()[:4])
    rules = shard.ShardingRules.fsdp(min_size=32)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(64, activation="relu"), nn.Dense(8))
    net.initialize()
    net(mx.nd.ones((8, 16)))
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     opt.Adam(learning_rate=1e-3), mesh=mesh,
                     sharding=rules)
    eng = InferStep(net, mesh=mesh, sharding=rules)
    rng = np.random.RandomState(0)
    batch = (nd.array(rng.randn(8, 16).astype("float32")),
             nd.array(rng.randint(0, 8, 8)))
    shapes = {n: tuple(p._data.data.shape)
              for n, p in net.collect_params().items()}
    return mesh, rules, step, eng, batch, shapes


def run_checks(mesh, rules, step, eng, batch, shapes) -> list:
    violations = []
    violations += check_step_placement(step, "TrainStep")
    violations += check_rules_coverage(rules, shapes, mesh)
    violations += check_post_step_placement(step, batch)
    violations += check_step_placement(eng, "InferStep")
    return violations


@register
class ShardingPlacementPass(AnalysisPass):
    name = "sharding-placement"
    ir = "jaxpr"
    description = ("every param carries its declared NamedSharding; "
                   "placements survive the donated step; no silent "
                   "replication fallback")

    def run(self, ctx):
        import jax

        if len(jax.devices()) < 4:
            return [self.finding(
                "rig", SHARDING_PY, 0, key="devices",
                message="sharding-placement needs >= 4 devices (set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=4 "
                "before jax import)")]
        setup = build_default_setup()
        return [self.finding("placement", SHARDING_PY, 0,
                             key=msg[:100], message=msg)
                for msg in run_checks(*setup)]
