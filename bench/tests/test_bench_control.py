"""What `correct` must catch, at a CPU-sized tensor.

The precision control — the reference one step below the configuration's
precision, in the program's place — reads above each cell's limit, while
the program reads below it. And with the timed path broken underneath,
a run with the look for a chip skipped reports ``correct`` false, once
for each fault a one-chip cell can have: a step that returns its state
unchanged, half the nonzeros left out with the sum doubled, and an answer
altered where it is produced. (No cell spans chips, so none can lose an
exchange between them.)
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import calibrate, run as bench_run
from bench.tests.test_bench_harness import CELLS, SPEC, small_spec


@pytest.fixture(autouse=True)
def fresh_traces():
    """The drivers' jitted programs are traced anew under each patch."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _limits(cell) -> dict:
    return {name: limit["limit"] for name, limit
            in bench_run.resolve(SPEC, cell).limits.items()}


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_program_passes(cell, tmp_path):
    (r,) = calibrate.readings(cell, [5], spec=small_spec(tmp_path),
                              chip=False)
    limits = _limits(cell)
    assert all(r["program"][name] <= limit for name, limit in limits.items())
    assert any(r["control"][name] > limit for name, limit in limits.items())


def _state_unchanged(monkeypatch):
    from repro.core import cpapr

    def mode_update(at, view, mode, lam, factors, phi_prev, first_outer,
                    pre_pi, p, plan):
        return (factors[mode], lam, phi_prev, jnp.asarray(False),
                jnp.asarray(p.l_max, jnp.int32), jnp.asarray(1.0))

    monkeypatch.setattr(cpapr, "_mode_update", mode_update)


def _halve(x):
    keep = (jnp.arange(x.shape[0]) % 2 == 0).astype(x.dtype)
    return x * keep * 2


def _half_the_nonzeros(monkeypatch):
    from repro.core import plan as plan_mod
    phi = plan_mod.execute_phi

    def at_half(at):
        return dataclasses.replace(at, values=_halve(at.values))

    def views_half(views):
        return {m: dataclasses.replace(v, values=_halve(v.values))
                for m, v in (views or {}).items()}

    def execute_phi(plan, at, view, B, mode, **kw):
        view = None if view is None else views_half({0: view})[0]
        return phi(plan, at_half(at), view, B, mode, **kw)

    monkeypatch.setattr(plan_mod, "execute_phi", execute_phi)


def _answer_altered(monkeypatch):
    from repro.core import plan as plan_mod
    phi = plan_mod.execute_phi

    def alter(out):
        return out.at[0, 0].add(jnp.max(jnp.abs(out)))

    monkeypatch.setattr(plan_mod, "execute_phi",
                        lambda *a, **k: alter(phi(*a, **k)))


FAULTS = {"state_unchanged": _state_unchanged,
          "half_the_nonzeros": _half_the_nonzeros,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_fault_makes_the_run_incorrect(cell, fault, tmp_path, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = bench_run.run(cell, 23, 0.2, False, spec=small_spec(tmp_path),
                        chip=False)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
