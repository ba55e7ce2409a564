"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the engine's dispatch (``_execute_sdtw``, what
``repro.core.sdtw`` and the Router's groups both run), and the rest of
the run is the harness's own, the look for a chip aside. The cells run
on one chip and exchange nothing between chips, so that fault has no
place here.
"""
import jax.numpy as jnp
import pytest

from bench import reference, run

FAULTS = {
    # a step that returns its state unchanged: the DP's initial carry
    "state_unchanged": lambda outs: [
        jnp.full_like(o, reference.BIG if i == 0 else -1)
        for i, o in enumerate(outs)],
    # half of the batch left out: its answers copied from the other half
    "half_batch": lambda outs: [
        jnp.concatenate([o[:(o.shape[0] + 1) // 2]] * 2)[:o.shape[0]]
        for o in outs],
    # an answer altered where it is produced
    "answer_altered": lambda outs: [outs[0].at[0].add(1), *outs[1:]],
}


# A served request holds one query, so its dispatch has no half to leave
# out.
CASES = [(cell, fault) for cell in ("ecg-offline-spans", "human-batch",
                                    "human-served")
         for fault in sorted(FAULTS)
         if (cell, fault) != ("human-served", "half_batch")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_not_correct(tiny_root, monkeypatch, cell, fault):
    from repro.core import engine
    real = engine._execute_sdtw

    def broken(req):
        out = real(req)
        outs = list(out) if isinstance(out, tuple) else [out]
        outs = FAULTS[fault](outs)
        return tuple(outs) if isinstance(out, tuple) else outs[0]

    monkeypatch.setattr(engine, "_execute_sdtw", broken)
    out = run.run_cell(tiny_root, cell, 11, 1.0, False, require_tpu=False)
    assert out["correct"] is False, out["checks"]
