"""At the configurations' rehearsal sizes, on the CPU: the system agrees
with its plain reference within the tolerance stated for those sizes, and
the comparison has teeth: the same reference computed in float8 in the
program's place (the control) falls outside it."""

import pytest

from _runs import in_process

CELLS = ["bert-base.pretrain-s128", "transformer-big.translate-closed"]


def _compared(out, of=None):
    return {r["number"]: r for r in out
            if r.get("note") == "compared" and r.get("of") == of}


@pytest.mark.parametrize("cell", CELLS)
def test_the_system_agrees_with_its_reference(capsys, cell):
    code, out = in_process(capsys, "--workload", cell, "--seed", "21",
                           "--seconds", "1", "--rehearse")
    assert code == 0
    numbers = _compared(out)
    assert len(numbers) >= 4
    assert all(r["inside"] for r in numbers.values()), numbers
    assert out[-1]["correct"] is True and out[-1]["failed"] == 0


@pytest.mark.parametrize("seed", [31, 32, 2**31 + 33])
def test_the_float8_control_fails_the_training_check(capsys, seed):
    code, out = in_process(capsys, "--workload", CELLS[0], "--seed",
                           str(seed), "--rehearse", "--control")
    assert code == 0 and out[-1] == {"note": "control", "seed": seed,
                                     "found_not_correct": True}
    numbers = _compared(out)
    # the gradient itself feels the precision; the loss at seeded weights
    # and the norms hardly do, which is why they do not carry the check
    assert not numbers["first_grad_worst_leaf_rel_diff"]["inside"]
    assert numbers["loss_step1_rel_gap"]["inside"]


@pytest.mark.parametrize("seed", [41, 42, 2**31 + 43])
def test_the_float8_control_fails_the_serving_check(capsys, seed):
    code, out = in_process(capsys, "--workload", CELLS[1], "--seed",
                           str(seed), "--seconds", "1", "--rehearse",
                           "--control")
    assert code == 0 and out[-1]["found_not_correct"] is True
    program = _compared(out)["widest_logit_gap"]
    control = _compared(out, of="control")["widest_logit_gap"]
    assert program["inside"] and not control["inside"]
    assert control["value"] > 3 * program["value"]
    assert program["positions"] == control["positions"] > 50


def test_a_dropped_term_fails_the_serving_reference():
    """The reference without its cross-attention is another model: the
    tokens that the full reference puts first lie far below its best."""
    import jax
    import numpy as np

    from perf.harness.loader import Benchmark
    from _runs import REPO

    b = Benchmark(REPO)
    cfg = b.config("transformer-big")
    cfg.update(cfg["rehearse"]["config"])
    ref = b.reference("transformer-big")
    w = ref.init_params(7, cfg)
    rng = np.random.default_rng(7)
    src = rng.integers(3, cfg["vocab_size"], (4, 16), dtype=np.int32)
    tgt = rng.integers(3, cfg["vocab_size"], (4, 16), dtype=np.int32)
    n = np.full((4,), 16, np.int32)
    with jax.default_matmul_precision("highest"):
        full = ref.logits(w, src, n, tgt, cfg)
        dropped = ref.logits(
            {k: (v * 0 if ".cross_attn.out_proj" in k else v)
             for k, v in w.items()}, src, n, tgt, cfg)
    best = np.asarray(full).argmax(-1)
    gap = np.asarray(dropped).max(-1) - np.take_along_axis(
        np.asarray(dropped), best[..., None], -1)[..., 0]
    assert gap.max() > 10 * cfg["tolerance"]["widest_logit_gap"]
