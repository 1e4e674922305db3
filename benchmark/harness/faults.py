"""Broken versions of the timed path, to show that `correct` catches them.

Each is a patch applied to the built system before any traffic flows
(`run.run(..., patch=...)`); the benchmark's own runs apply none.

- `control`: the step that would tempt a later PR, sampling the sketch
  lane by host, as sampled flow export does: the records of half the
  client hosts (by a hash of the source address) are kept, and they
  stand in for the dropped ones, so every chunk keeps its row count and
  the totals stay exact. It breaks the stated guarantees that every
  record sent is counted and that distinct clients are estimated within
  3 standard errors.
- `state_unchanged`: the update step hands back the state it was given.
- `half_batch`: each chunk's second half is left out and its first half
  stands in for it (the rest's mean in place of the whole).
- `answer_altered`: every published window's row count is off by one
  where the window is produced.
"""

from __future__ import annotations

import numpy as np


def control(served) -> None:
    sketch = served.sketch
    process = sketch.process

    def sampled(chunks):
        out = []
        for stream, idx, cols, *rest in chunks:
            src = np.asarray(cols["ip_src"]).astype(np.uint32)
            with np.errstate(over="ignore"):
                kept = np.flatnonzero(((src * np.uint32(0x9E3779B1))
                                       >> np.uint32(16)) & np.uint32(1))
            if len(kept):
                pick = kept[np.arange(len(src)) % len(kept)]
                cols = {k: np.asarray(v)[pick] for k, v in cols.items()}
            out.append((stream, idx, cols, *rest))
        process(out)

    sketch.process = sampled


def state_unchanged(served) -> None:
    import jax
    import jax.numpy as jnp

    sketch = served.sketch
    update = sketch._timed_update

    def frozen(key, fn, state, *rest):
        before = jax.tree.map(jnp.copy, state)
        out = update(key, fn, state, *rest)
        return (before,) + tuple(out[1:])

    sketch._timed_update = frozen


def half_batch(served) -> None:
    sketch = served.sketch
    process = sketch.process

    def halved(chunks):
        out = []
        for stream, idx, cols, *rest in chunks:
            n = len(next(iter(cols.values())))
            h = max(1, n // 2)
            pick = np.arange(n) % h
            out.append((stream, idx, {k: v[pick] for k, v in cols.items()},
                        *rest))
        process(out)

    sketch.process = halved


def answer_altered(served) -> None:
    bus = served.sketch.snapshot_bus
    publish = bus.publish

    def altered(state, step, **kw):
        return publish(state._replace(rows_seen=state.rows_seen + 1), step,
                       **kw)

    bus.publish = altered


PATCHES = {f.__name__: f for f in (control, state_unchanged, half_batch,
                                   answer_altered)}
