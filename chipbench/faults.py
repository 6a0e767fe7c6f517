"""Faults planted in the program under test, to show that the comparison
deciding ``correct`` catches each: the harness's tests plant them at a
small size, and ``chipbench/control.py --fault`` at a cell's own size on
the chip.  Each is a context manager that breaks the timed path (or the
compressor, for the prefixes made during set-up) while it is entered.
"""

from __future__ import annotations

from contextlib import contextmanager

from chipbench import bench


@contextmanager
def _after_setup(plant):
    """Plant ``plant(run)`` into each run's engine once set-up is done."""
    setup = bench.Run.setup

    def broken(self):
        out = setup(self)
        plant(self)
        return out

    bench.Run.setup = broken
    try:
        yield
    finally:
        bench.Run.setup = setup


def _altered_token(run):
    eng = run.engine
    step, vocab = eng._decode_greedy, eng.cfg.vocab_size

    def decode(*args):
        ids, cache = step(*args)
        return (ids + 1) % vocab, cache

    eng._decode_greedy = decode


def _unchanged_state(run):
    eng = run.engine
    step = eng._decode_greedy

    def decode(params, cache, *rest):
        ids, _ = step(params, cache, *rest)
        return ids, cache

    eng._decode_greedy = decode


def _wrong_task(run):
    """Every slot is seated with the next task's compressed prefix."""
    eng, tasks = run.engine, len(run.traffic.shots)
    seat = eng.seat_prefix

    def seat_other(slot, name):
        k = int(name.removeprefix("task"))
        seat(slot, bench.task_name((k + 1) % tasks))

    eng.seat_prefix = seat_other


def altered_token():
    """A served token altered where the decode step produces it."""
    return _after_setup(_altered_token)


def unchanged_state():
    """A decode step that returns the KV cache it was given."""
    return _after_setup(_unchanged_state)


def wrong_task():
    """A slot served another task's compressed prefix."""
    return _after_setup(_wrong_task)


@contextmanager
def zero_xattn():
    """The compressor's cross-attention returns zeros, so every prefix
    forgets its shots.  Planted before set-up compresses the tasks."""
    import jax.numpy as jnp
    from repro.kernels import ops

    saved = ops.memcom_xattn
    ops.memcom_xattn = lambda q, k, v, **_kw: jnp.zeros_like(q)
    try:
        yield
    finally:
        ops.memcom_xattn = saved


FAULTS = {f.__name__: f for f in (altered_token, unchanged_state,
                                  wrong_task, zero_xattn)}
