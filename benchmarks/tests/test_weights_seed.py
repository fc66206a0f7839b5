"""A configuration may fix the seed its runs are made from
(`model.run_seed`, beside `graph.graph_seed`): weights, batches and
every sampling key, for the program and the reference alike. The `keye`
cell does, and no `--seed` moves it. Without the key a run is what it
was. `proof.py` still reads a cell over many weights and many batches,
through the arguments it hands both sides. Rehearsal sizes, on the CPU."""

import argparse

import json

import numpy as np
import pytest

import run as harness
import weights

PINNED = "keye-vl2-30b-a3b-ep8.train-long-tokens"
FREE = "deepwalk-products.train-device"
SEEDS = (2147483711, 11)


@pytest.fixture(scope="module")
def staged():
    """Each cell staged at rehearsal size, with its reference's tables,
    loss and learning rate under `ref`."""
    out = {}
    for cell in (PINNED, FREE):
        st = harness.stage(cell, rehearse=True)
        tables, loss_fn = st["reference"].make(st["config"], st["mix"], st["graph"])
        lr = st["config"]["optimizer"]["learning_rate"]
        out[cell] = {**st, "ref": (loss_fn, tables, st["spec"], lr)}
    return out


def _reference(st, seed, **kw):
    loss_fn, tables, spec, lr = st["ref"]
    return st["train"].first_steps(loss_fn, tables, spec, seed, lr, **kw)


def _both_sides(st, seed, weights_seed=None):
    """The program's weights as its Estimator got them, its first three
    steps, and the reference's, from the batches of `seed` and the
    weights of `weights_seed` (`seed`'s own where none is given)."""
    config, spec = st["config"], st["spec"]
    wseed = seed if weights_seed is None else weights_seed
    est = harness.make_estimator(st["built"], config, st["mix"], spec, seed, weights_seed)
    given = {k: np.asarray(v) for k, v in weights.flatten(est.params).items()}
    got = harness.program_first_steps(est, spec, wseed)
    return given, got, _reference(st, seed, weights_seed=weights_seed)


def _equal(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(np.array_equal(a[k], b[k]) for k in a)


def _correct(st, got, want):
    compared = st["train"].compare(got, want)
    compared.update(window_compiles=0, failed_steps=0)
    return harness.decide(compared, st["limits"])


def test_one_model_over_two_batch_seeds_as_proof_runs_it(staged):
    """`proof.py --weights-seeds`: bit-equal weights on both sides
    whatever the batches' seed, different first batches, `correct`."""
    st = staged[PINNED]
    fixed = st["config"]["model"]["run_seed"]
    made = {k: np.asarray(v) for k, v in weights.make_params(st["spec"], fixed).items()}
    runs = [_both_sides(st, seed, weights_seed=fixed) for seed in SEEDS]
    for given, got, want in runs:
        assert _equal(given, made)  # the program's model is the one asked for
        # ... and the reference's: same weights, same batches, same numbers
        table, ok = _correct(st, got, want)
        assert ok, table
    # one model, two batches: the first loss and the first gradient differ
    (_, got_a, want_a), (_, got_b, want_b) = runs
    assert got_a["loss"][0] != got_b["loss"][0]
    assert want_a["loss"][0] != want_b["loss"][0]
    assert want_a["grad_norm"] != want_b["grad_norm"]


def test_the_reference_on_other_weights_is_not_correct(staged):
    """Were the two sides to disagree on which weights a run has, the
    comparison would say so."""
    st = staged[PINNED]
    seed = SEEDS[0]
    _, got, _ = _both_sides(st, seed, weights_seed=st["config"]["model"]["run_seed"])
    other = _reference(st, seed)  # on the batch seed's own weights
    table, ok = _correct(st, got, other)
    assert not ok, table


def test_with_its_run_seed_fixed_no_seed_moves_the_cell(staged):
    """`keye` as configured: the same weights, batches and compared
    numbers whatever `--seed` the driver draws."""
    st = staged[PINNED]
    fixed = st["config"]["model"]["run_seed"]
    assert [weights.run_seed(st["config"], seed) for seed in SEEDS] == [fixed, fixed]
    outs = []
    for seed in SEEDS:
        args = argparse.Namespace(
            workload=PINNED, seed=seed, seconds=0.3, trace=0, rehearse=True, keep_trace=""
        )
        outs.append(harness.run(args))
    for seed, out in zip(SEEDS, outs):
        assert out["correct"], out["compared"]
        assert (out["run"]["seed"], out["run"]["run_seed"]) == (seed, fixed)
    assert outs[0]["compared"] == outs[1]["compared"]
    # and that run is the run of `--seed` = `run_seed` with no key at all
    given, got, want = _both_sides(st, fixed)
    assert _equal(given, {k: np.asarray(v) for k, v in weights.make_params(st["spec"], fixed).items()})
    compared = st["train"].compare(got, want)
    assert all(outs[0]["compared"][k]["value"] == v for k, v in compared.items())


def test_without_the_key_a_run_is_the_seeds_as_before(staged):
    st = staged[FREE]
    assert "run_seed" not in st["config"]["model"]
    assert weights.run_seed(st["config"], SEEDS[0]) == SEEDS[0]
    seen = []
    for seed in SEEDS:
        given, got, want = _both_sides(st, seed)
        made = weights.make_params(st["spec"], seed)  # the parent's call, unchanged
        assert _equal(given, {k: np.asarray(v) for k, v in made.items()})
        # the reference called as before this key existed gives the same bits
        assert _reference(st, seed) == want
        seen.append(given)
    assert not _equal(*seen)


@pytest.mark.parametrize(
    "cell, argv, want",
    [
        (PINNED, ["--seeds", "5", "--weights-seeds", "1,2"], [(5, 1), (5, 2)]),
        (PINNED, ["--seeds", "5,6"], [(5, None), (6, None)]),  # the configuration's
        (FREE, ["--seeds", "5,6", "--weights-seeds", "7"], [(5, 7), (6, 7)]),
        (FREE, ["--seeds", "5,6"], [(5, 5), (6, 6)]),
    ],
)
def test_proof_runs_its_loop_once_a_weights_seed(cell, argv, want, capsys):
    import proof

    fixed = harness.resolve(cell)["config"]["model"].get("run_seed")
    assert proof.main(["--workload", cell, "--controls", "0", "--rehearse", *argv]) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert "memory_peak_bytes" in rows.pop()
    assert [(r["seed"], r["weights_seed"]) for r in rows] == [
        (s, fixed if w is None else w) for s, w in want
    ]
    assert all(set(r["program"]) >= {"loss_step1", "change_norm_gap"} for r in rows)
