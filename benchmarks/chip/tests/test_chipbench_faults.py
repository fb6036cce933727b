"""The comparison that decides ``correct`` catches a broken timed path:
each fault is planted under the app driver's step and a whole run is driven
on the CPU at a tiny size."""
import dataclasses

import numpy as np
import pytest

import chipbench_tiny as T


class Unchanged:
    """The step runs, and its state is put back: state left unchanged."""

    def __init__(self, sess, attr):
        self.s, self.attr = sess, attr

    def __getattr__(self, k):
        return getattr(self.s, k)

    def step(self):
        prev = getattr(self.s, self.attr)
        failed = self.s.step()
        setattr(self.s, self.attr, prev)
        return failed


def _particles(state):
    """The MD state's particle set; a reuse state's is its inner one."""
    return _particles(state.inner) if hasattr(state, "inner") else state.ps


def _with_particles(state, ps):
    if hasattr(state, "inner"):
        return dataclasses.replace(
            state, inner=_with_particles(state.inner, ps))
    return dataclasses.replace(state, ps=ps)


class Altered(Unchanged):
    """One value of the step's answer is altered where it is produced."""

    def step(self):
        failed = self.s.step()
        state = getattr(self.s, self.attr)
        if self.attr == "w":
            setattr(self.s, "w", state.at[1, 2, 3, 0].add(
                0.1 * float(np.abs(state).max())))
        else:
            ps = _particles(state)
            f = ps.props["f"]
            ps = ps.with_prop("f", f.at[5, 1].add(float(np.abs(f).max())))
            self.s.state = _with_particles(state, ps)
        return failed


class NoDrift(Unchanged):
    """The MD step computes its forces and kicks, but its positions are
    put back: the integrator's drift is lost where it is produced."""

    def step(self):
        prev = _particles(self.s.state).x
        failed = self.s.step()
        state = self.s.state
        ps = dataclasses.replace(_particles(state), x=prev)
        self.s.state = _with_particles(state, ps)
        return failed


ATTR = {"md": "state", "vic": "w"}


@pytest.mark.parametrize("fault", [Unchanged, Altered])
@pytest.mark.parametrize("app", ["md", "vic"])
def test_fault_is_not_correct(tmp_path, app, fault):
    root = T.write_tiny(tmp_path)
    res = T.run_tiny(root, T.CELL_OF_APP[app],
                     wrap=lambda s: fault(s, ATTR[app]))
    assert res["correct"] is False, res["checks"]


def test_lost_drift_is_not_correct(tmp_path):
    root = T.write_tiny(tmp_path)
    res = T.run_tiny(root, T.CELL_OF_APP["md"],
                     wrap=lambda s: NoDrift(s, "state"))
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["pos_err"]["value"] > (
        res["checks"]["pos_err"]["limit"])

