"""Crash + parallel recovery demo: the Poplar journal guarantees that a
long-running computation resumes from the newest *committed* step marker —
shard records from a half-flushed step are provably uncommitted and ignored
(recoverability, paper §3.1/§5, applied to a journaled pytree).

The journaled state is a plain dict of arrays advanced by a fixed numpy
step, so the resumed run can be checked bit for bit against a run that
never crashed.
"""

import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.journal import PoplarCheckpointManager, restore_latest, to_pytree

N_STEPS = 12
DURABLE_STEP = 9     # waited on before the crash: must survive it
RESUME_STEPS = 3


def init_state():
    rng = np.random.default_rng(0)
    return {
        "w": rng.normal(size=(64, 32)).astype(np.float32),
        "b": np.zeros(32, np.float32),
        "mu": np.zeros((64, 32), np.float32),
        "cursor": np.asarray(0, np.int64),
    }


def advance(state):
    """One deterministic update: a momentum step towards a fixed target."""
    target = np.float32(0.5)
    grad = state["w"] - target
    mu = np.float32(0.9) * state["mu"] + grad
    return {
        "w": state["w"] - np.float32(0.01) * mu,
        "b": state["b"] + np.float32(0.01) * grad.mean(axis=0),
        "mu": mu,
        "cursor": state["cursor"] + 1,
    }


def main() -> None:
    journal = tempfile.mkdtemp(prefix="crash_demo_")
    try:
        run(journal)
    finally:
        shutil.rmtree(journal, ignore_errors=True)


def run(journal: str) -> None:
    state = init_state()
    mgr = PoplarCheckpointManager(journal, n_lanes=3, flush_interval=1e-3)

    print(f"== phase 1: advance {N_STEPS} steps, journaling every step ==")
    for step in range(N_STEPS):
        state = advance(state)
        h = mgr.save(step, state, {"cursor": int(state["cursor"])})
        h.wait()
        if step == DURABLE_STEP:
            mgr.wait_for_commit(DURABLE_STEP, timeout=30)
    committed_before = mgr.last_committed_step()
    print(f"   last committed step before crash: {committed_before}")
    print("== CRASH (loggers killed, volatile buffers lost) ==")
    mgr.crash()

    print("== phase 2: parallel recovery from journal lanes ==")
    out = restore_latest(journal)
    assert out is not None
    rstep, flat, meta = out
    print(f"   restored step {rstep} (meta {meta}) — "
          f"{'all' if rstep == N_STEPS - 1 else 'volatile tail dropped;'} "
          "consistent by construction")
    assert rstep >= DURABLE_STEP
    restored = to_pytree(flat, init_state())

    # the restored state is exactly the one the uncrashed run had at rstep
    expect = init_state()
    for _ in range(rstep + 1):
        expect = advance(expect)
    for k in expect:
        np.testing.assert_array_equal(restored[k], expect[k])
    assert int(restored["cursor"]) == rstep + 1 == meta["cursor"]

    print("== phase 3: resume ==")
    resumed = restored
    for step in range(rstep + 1, rstep + 1 + RESUME_STEPS):
        resumed = advance(resumed)
        expect = advance(expect)
        print(f"   step {step} |w - 0.5| {float(np.abs(resumed['w'] - 0.5).mean()):.6f}")
    for k in expect:
        np.testing.assert_array_equal(resumed[k], expect[k])
    print("OK — resumed exactly at the recovered cursor", int(resumed["cursor"]))


if __name__ == "__main__":
    main()
