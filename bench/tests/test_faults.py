"""The comparison catches a broken timed path.

Each case drives the rest of a benchmark run (``run.run_cell``: set-up,
the closed-loop window, the comparison) on the CPU at a small size, past
the harness's look for a chip, with the served path broken underneath:
the service returns its previous answers unchanged, leaves half of the
requests without rows, or alters one row of one answer.  The control,
the reference one data version behind in the program's place, must fail
too.  The exchange between chips cannot be left out: every cell runs on
one chip.
"""
import jax.numpy as jnp
import pytest

import run
from control import StaleReference

SMALL = {"rows_per_relation": 2**9}


def _break(monkeypatch, fault):
    from repro.service import batcher

    real_tick = batcher.SGFService.tick
    last = {}

    def tick(self):
        done = real_tick(self)
        reqs = self.last_batch.requests
        if fault == "unchanged":
            fresh = [dict(r.outputs) for r in reqs]
            if "outputs" in last:
                for r, old in zip(reqs, last["outputs"]):
                    r.outputs = old
            last["outputs"] = fresh
        elif fault == "half":
            for r in reqs[::2]:
                r.outputs = {k: v.with_mask(jnp.zeros_like(v.valid))
                             for k, v in r.outputs.items()}
        elif fault == "altered":
            name, rel = next(iter(reqs[0].outputs.items()))
            flat = rel.valid.reshape(-1)
            i = int(jnp.argmax(flat))
            data = rel.data.reshape(-1, rel.arity).at[i, 0].add(1)
            reqs[0].outputs[name] = type(rel)(rel.name, data.reshape(rel.data.shape),
                                             rel.valid)
        return done

    monkeypatch.setattr(batcher.SGFService, "tick", tick)


@pytest.mark.parametrize("cell", [c["name"] for c in run.load_spec()["workloads"]])
@pytest.mark.parametrize("fault", [None, "unchanged", "half", "altered", "control"])
def test_comparison(monkeypatch, cell, fault):
    factory = StaleReference if fault == "control" else None
    if fault not in (None, "control"):
        _break(monkeypatch, fault)
    result = run.run_cell(run.load_spec(), cell, 2**31 + 11, 0.5, False,
                          overrides=SMALL, server_factory=factory)
    checks = result["checks"]
    assert checks["checked_requests"]["value"] >= 1
    if fault is None:
        assert result["correct"], checks
        assert checks["mismatched_rows"]["value"] == 0
    else:
        assert not result["correct"], checks
        assert checks["mismatched_rows"]["value"] > 0
    assert list(result)[-1] == "checks"
