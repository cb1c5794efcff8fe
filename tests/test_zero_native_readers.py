"""What reads a ``ParallelTrainer``'s ZeRO state from outside, over a
plan with native buckets (PR 30): the benchmark's own driver
(``perfbench/drivers/parallel_trainer.py`` — not edited by that PR)
slices optimizer slots out of ``opt_state["fused"]`` by
``Bucket.names / shapes / offsets / sizes``, which a one-leaf
``(rows, C)`` buffer with ``offsets == [0, n]`` must still satisfy.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PB = os.path.join(ROOT, "perfbench")


@pytest.fixture(scope="module")
def lm_driver():
    """The driver of ``opt1p3b-train-s2048`` at its own ``rehearse``
    sizes (a 2-layer ``TransformerLM``, hidden 256), built as
    ``perfbench/run.py`` builds it, after two steps.  At these sizes
    the default 4 MiB cap would group the matrices (a grouped bucket is
    flat), so the cap is set where each is alone in its bucket, as every
    matrix of the real cell is."""
    import jax
    sys.path.insert(0, PB)
    env = pytest.MonkeyPatch()
    env.setenv("MXNET_PARALLEL_BUCKET_BYTES", "65536")
    env.setenv("MXNET_PARALLEL_BUCKET_FIRST_BYTES", "65536")
    try:
        import loader
        cell = loader.Bench(ROOT).cell("opt1p3b-train-s2048")
        config = cell.config_for(rehearse=True)
        specs = cell.reference().leaf_specs(config)
        driver = cell.driver().Driver(config, jax.devices()[:1],
                                      rehearse=True)
        rng = np.random.RandomState(11)
        weights = {n: (rng.randn(*s) * 0.02).astype(np.float32)
                   for n, s, _init in specs}
        driver.build(weights)
    finally:
        sys.path.remove(PB)
        env.undo()
    mx = driver.mx
    b, t = int(config["batch_size"]), int(config["seq_len"])
    toks = rng.randint(0, int(config["vocab_size"]), (b, t + 1))
    x, y = toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)
    for _ in range(2):
        driver.trainer.step(mx.nd.array(x, dtype="int32"),
                            mx.nd.array(y, dtype="int32"))
    return driver, weights


def test_the_lm_cell_has_native_buckets(lm_driver):
    driver, weights = lm_driver
    plan = driver.trainer.bucket_plan
    native = {n for b in plan if b.layout == "native" for n in b.names}
    by_ref = {driver._names[n] for n in native}
    assert by_ref == {n for n, w in weights.items() if w.ndim == 2}
    total = sum(w.nbytes for w in weights.values())
    assert sum(b.nbytes for b in plan if b.layout == "native") > 0.99 * total
    for b in plan:
        buf = driver.trainer.opt_state["fused"]["mean"]["b%d" % b.index]
        assert buf.shape == b.buffer_shape
        if b.layout == "native":
            assert buf.ndim == 2 and b.offsets == [0, b.n] == [0, b.padded_n]


@pytest.mark.parametrize("slot", ["mean", "var"])
def test_the_benchmarks_driver_reads_native_slots(lm_driver, slot):
    """``Driver.slots()`` per leaf equals ``state_dict()``'s per-param
    slot, shape and bits, for native and flat buckets alike."""
    driver, weights = lm_driver
    got = driver.slots(slot)
    want = driver.trainer.state_dict()["slots"][slot]
    assert sorted(got) == sorted(weights)
    for pname, rname in driver._names.items():
        assert got[rname].shape == weights[rname].shape, rname
        np.testing.assert_array_equal(got[rname], want[pname], err_msg=rname)
    assert any(np.abs(v).max() > 0 for v in got.values())


def test_the_benchmarks_driver_reads_the_leaves(lm_driver):
    driver, weights = lm_driver
    got = driver.leaves()
    want = driver.trainer.state_dict()["params"]
    assert sorted(got) == sorted(weights)
    moved = 0
    for pname, rname in driver._names.items():
        assert got[rname].shape == weights[rname].shape, rname
        np.testing.assert_array_equal(got[rname], want[pname], err_msg=rname)
        moved += not np.array_equal(got[rname], weights[rname])
    assert moved > len(weights) // 2    # two steps were taken
