"""`correct` at rehearsal size on the CPU: a sound run passes; the
control (the reference held in bfloat16, in the program's place) and
each fault a training cell can have come out as not correct. The
harness's look for a chip is skipped (--rehearse); the rest of a run is
the real thing."""

import argparse
import dataclasses

import pytest

import run as harness

CELLS = ["sage-products-id.train-device", "deepwalk-products.train-device"]


def _args(cell, seed):
    return argparse.Namespace(
        workload=cell, seed=seed, seconds=0.3, trace=0, rehearse=True, keep_trace=""
    )


def unchanged_state(est, built):
    """A step that returns its state unchanged (the loss still comes)."""
    import jax
    import jax.numpy as jnp

    real = est._train_step

    def factory():
        step = real()

        def fake(params, opt_state, rngs, *batch):
            copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
            _, _, loss, metric = step(copy(params), copy(opt_state), rngs, *batch)
            return params, opt_state, loss, metric

        return fake

    est._train_step = factory


def half_batch(est, built):
    """Half of the batch left out, the mean taken over the rest."""
    import jax.numpy as jnp

    flow = built["flow"]
    if built["feature_cache"] is None:  # skip-gram: mask out half of the pairs
        sample = flow.sample

        def half(key):
            batch = dict(sample(key))
            n = batch["mask"].shape[0]
            batch["mask"] = batch["mask"] & (jnp.arange(n) < n // 2)
            return batch

        flow.sample = half
        return
    import optax

    base = type(est.model)

    class Half(base):
        def __call__(self, batch):
            emb = self.embed(batch)
            per = jnp.sum(
                optax.sigmoid_binary_cross_entropy(self.out(emb), batch.labels), -1
            )
            return emb, jnp.mean(per[: per.shape[0] // 2]), "f1", jnp.zeros(())

    fields = {
        f.name: getattr(est.model, f.name)
        for f in dataclasses.fields(est.model)
        if f.name not in ("parent", "name")
    }
    est.model = Half(**fields)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = harness.run(_args(cell, 2147483700))
    assert out["correct"], out["compared"]
    assert out["metrics"] == {}  # a rehearsal names no device metric
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
def test_planted_fault_is_not_correct(cell, fault):
    out = harness.run(_args(cell, 11), plant=fault)
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_control_is_not_correct(cell):
    import jax.numpy as jnp

    import graphs

    r = harness.resolve(cell)
    config = harness.merge(r["config"], r["config"]["rehearse"])
    family = harness.load_module("families", config["family"])
    ref = harness.load_module("reference", family.REFERENCE)
    train = harness.load_module("reference", "train")
    graph = graphs.build(config["graph"])
    spec = ref.param_spec(config, graph)
    tables, loss_fn = ref.make(config, r["mix"], graph)
    lr = config["optimizer"]["learning_rate"]
    for seed in (3, 4, 5):
        want = train.first_steps(loss_fn, tables, spec, seed, lr)
        ctrl = train.first_steps(loss_fn, tables, spec, seed, lr, dtype=jnp.bfloat16)
        compared = train.compare(ctrl, want)
        compared.update(window_compiles=0, failed_steps=0)
        table, ok = harness.decide(compared, r["limits"])
        assert not ok, table
