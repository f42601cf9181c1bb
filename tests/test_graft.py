"""Driver contract: __graft_entry__.entry() compiles; dryrun_multichip
runs in a child on its own 8-device virtual CPU mesh."""

import sys

import jax

sys.path.insert(0, "/root/repo")
import __graft_entry__ as graft  # noqa: E402


def test_entry_compiles_and_commits():
    import numpy as np

    fn, args = graft.entry()
    state, out = jax.jit(fn)(*args)
    committed = np.asarray(out.committed)  # [R, P], replica-invariant
    assert committed[:, :4].all()


def test_dryrun_multichip_executes(capfd):
    graft.dryrun_multichip(8)
    # The CPU-mesh path names itself: a green run must never read as a
    # multi-chip result.
    assert "virtual CPU mesh" in capfd.readouterr().out
