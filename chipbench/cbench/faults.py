"""Faults planted underneath the timed path, for the readings that set
a cell's upper limits (``calibrate.py faults``) and for the tests that
see a broken program come out as ``correct: false``.

Each fault is a context manager that patches the program where the
fault would sit and, while it is planted, keeps the program's
executable caches out of the way: the in-process caches are cleared on
entry and exit, and the whole-executable disk layer neither loads nor
stores (its key is the program's configuration, not its graph, so it
would serve the sound executable or keep the broken one).  XLA's own
module cache is keyed by the graph and stays on.  Not used by a
benchmark run.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict
from unittest import mock

import jax
import jax.numpy as jnp


@contextlib.contextmanager
def _isolated(*patches):
    from repro.api import clear_executable_caches
    from repro.core import compilecache
    from repro.serving.anomaly import engine
    with contextlib.ExitStack() as stack:
        stack.enter_context(mock.patch.object(
            compilecache, "load_executable", lambda fp: None))
        stack.enter_context(mock.patch.object(
            compilecache, "store_executable", lambda fp, c: None))
        for p in patches:
            stack.enter_context(p)
        clear_executable_caches()
        engine.clear_score_cache()
        try:
            yield
        finally:
            clear_executable_caches()
            engine.clear_score_cache()


def state_unchanged():
    """The combine returns a zero update: every step leaves the global
    model where it was."""
    from repro.core import aggregation
    orig = aggregation.stacked_streaming_mean

    def no_update(gs, ns, unroll=16):
        n, g = orig(gs, ns, unroll)
        return n, jax.tree.map(jnp.zeros_like, g)
    return _isolated(mock.patch.object(
        aggregation, "stacked_streaming_mean", no_update))


def half_batch():
    """Each device's loss leaves out the second half of its rows and
    takes the mean over the rest: the ``loss`` of every body's program
    class (``program_loss_class()`` of each file in ``bodies/``)."""
    from cbench import harness
    classes = {harness.body(k).program_loss_class()
               for k in harness.body_kinds()}

    def halved(orig):
        def half(self, params, x, valid, key=None):
            keep = jnp.arange(valid.shape[-1]) < valid.shape[-1] // 2
            return orig(self, params, x, valid * keep, key)
        return half
    return _isolated(*(mock.patch.object(cls, "loss", halved(cls.loss))
                       for cls in classes))


def masks_ignored():
    """Failure traces are read as all-alive: dead devices and heads
    keep training and combining."""
    from repro.core import baselines, simulate

    def all_alive(trace, num_devices, epoch):
        return jnp.ones((num_devices,), jnp.float32)
    return _isolated(
        mock.patch.object(simulate, "trace_alive_mask", all_alive),
        mock.patch.object(baselines, "trace_alive_mask", all_alive))


def fallback_off():
    """FL's isolated fallback is not reported: a dead server's frozen
    global model stands for the surviving devices."""
    from repro.core import campaign
    orig = campaign._post_process_arrays

    def no_fallback(track_iso, out, test_y, target_loss):
        return orig(False, out, test_y, target_loss)
    return _isolated(mock.patch.object(
        campaign, "_post_process_arrays", no_fallback))


def misrouted():
    """The service scores every window with the global model, dead head
    or not."""
    from repro.serving.anomaly.bank import ModelBank
    return _isolated(mock.patch.object(
        ModelBank, "row_index", lambda self, client, failover: 0))


def score_altered():
    """Served scores come out 1% high."""
    from repro.serving.anomaly import engine
    orig = engine.score_executable

    def altered(model, avals):
        compiled, times = orig(model, avals)
        return (lambda *a: compiled(*a) * 1.01), times
    return _isolated(mock.patch.object(engine, "score_executable",
                                       altered))


#: traffic kind -> the faults its cells can have
FAULTS: Dict[str, Dict[str, Callable]] = {
    "campaign": {"state_unchanged": state_unchanged,
                 "half_batch": half_batch,
                 "masks_ignored": masks_ignored,
                 "fallback_off": fallback_off},
    "serve": {"misrouted": misrouted, "score_altered": score_altered},
}
