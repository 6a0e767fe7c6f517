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


@contextmanager
def _no_kv_write():
    """``paged_scatter`` writes no row: it returns the pool it was given."""
    from repro.kernels import ops

    saved = ops.paged_scatter
    ops.paged_scatter = lambda pool, *_a, **_kw: pool
    try:
        yield
    finally:
        ops.paged_scatter = saved


def _unchanged_state(run):
    """The decode step is built again, donating its cache as the engine's
    own does, from the same function traced with a ``paged_scatter`` that
    writes no row: its new tokens' keys and values never reach the pool,
    and it hands back the pool it was given."""
    import jax

    eng = run.engine
    step = eng._decode_greedy.__wrapped__

    def decode(*args):
        with _no_kv_write():
            return step(*args)

    decode.__name__ = decode.__qualname__ = step.__name__
    eng._decode_greedy = jax.jit(
        decode, donate_argnums=(1,) if eng.donate_cache else ())


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
    """A decode step whose KV writes write nothing: the pool comes back
    as it went in."""
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
