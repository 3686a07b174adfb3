"""Faults planted in the timed path of cells whose model mixes
sliding-window and full attention with routed experts, each a context
manager that patches the program underneath a run (as ``faults.py`` does
for the other cells).  The tests (``test_bench_mellum2.py``) use them to
show that ``correct`` comes out false when the program is wrong; the
benchmark's own runs never do.  The control is ``faults.serve_control``.

* ``window_short``   — the sliding window one position short, in prefill's
  mask and in decode's ring read;
* ``ring_at_pos``    — decode writes the ring at the position, not at the
  position mod the window: once a sequence outgrows the ring its new keys
  are dropped;
* ``capacity_drops`` — the experts served with capacity (GShard dispatch,
  capacity factor 0.5), so busy experts drop tokens;
* ``yarn_off``       — the full layers rotated with the default frequencies,
  without YaRN's blend and attention factor.
"""
from __future__ import annotations

import contextlib
import dataclasses

FAULTS = {}


def fault(fn):
    FAULTS[fn.__name__] = contextlib.contextmanager(fn)
    return FAULTS[fn.__name__]


def _patch(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    return lambda: setattr(obj, name, old)


@fault
def window_short():
    from repro.models import transformer as T
    window = T._window

    def short(cfg, kind):
        w = window(cfg, kind)
        return w - 1 if w else w
    undo = _patch(T, "_window", short)
    try:
        yield
    finally:
        undo()


@fault
def ring_at_pos():
    from repro.models import transformer as T
    undo = _patch(T, "_ring_slot", lambda pos, W: pos)
    try:
        yield
    finally:
        undo()


@fault
def capacity_drops():
    from repro.models import moe as X
    dropless = X.moe_dropless

    def with_capacity(params, x, cfg, valid=None, layer=None):
        capped = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.5))
        one = params if layer is None else {
            k: (v[layer] if k in X.EXPERT_WEIGHTS else v)
            for k, v in params.items()}
        out, _ = X.moe_apply(one, x, capped)
        return out, dropless(params, x, cfg, valid, layer)[1]
    undo = _patch(X, "moe_dropless", with_capacity)
    try:
        yield
    finally:
        undo()


@fault
def yarn_off():
    from repro.models import transformer as T
    ropes = T._ropes
    undo = _patch(T, "_ropes", lambda cfg, positions: ropes(
        dataclasses.replace(cfg, yarn=None), positions))
    try:
        yield
    finally:
        undo()
