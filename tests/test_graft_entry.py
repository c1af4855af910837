import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_entry_compiles():
    import jax

    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 10)


import pytest


@pytest.mark.slow
def test_dryrun_multichip_8():
    """Slow lane: the subprocess-bootstrapped 8-chip dryrun costs ~60 s
    on a 2-CPU box and the fast lane keeps entry coverage via
    ``test_entry_compiles``; the dryruns (8 and 32) ride the slow tier."""
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


@pytest.mark.slow
def test_dryrun_multichip_32():
    """Mesh shapes beyond the 8-device habit (r4 VERDICT #8): the full
    parallel stack (client shards, ring/flash SP, TP, EP all_to_all,
    GPipe PP) on a 32-virtual-device mesh — catches any hardcoded
    8-assumption (divisibility, stage counts, microbatch math) before a
    real pod exists. Subprocess-bootstrapped, so the in-process backend
    (usually 8 CPU devices under conftest) doesn't constrain it."""
    import __graft_entry__ as ge

    ge.dryrun_multichip(32)
