"""Names of the host spans :meth:`Engine.step` writes into a profiler
trace (``jax.profiler.TraceAnnotation``), on the same clock as the device's
operations. ``STEP`` covers the whole call; the phases nest inside it, in
the order they run. With no trace running a span costs about a
microsecond, so they are always on.

* ``engine.expire`` — deadline expiry and forced preemption;
* ``engine.grow`` — page growth of the active lanes (``_ensure_pages``);
* ``engine.admit`` — admission, with the admitted request ids as the
  ``rids`` stat (space-separated);
* ``engine.pack`` — the step's rows: per-slot token counts, chunk tokens,
  chunk page spans and the block accounting;
* ``engine.upload`` — host arrays to the device, block tables included;
* ``engine.dispatch`` — the jitted step call returning (asynchronous);
* ``engine.sync`` — waiting for the step's tokens (the one host sync);
* ``engine.retire`` — emitting tokens and finishing requests.
"""

STEP = "engine.step"
EXPIRE = "engine.expire"
GROW = "engine.grow"
ADMIT = "engine.admit"
PACK = "engine.pack"
UPLOAD = "engine.upload"
DISPATCH = "engine.dispatch"
SYNC = "engine.sync"
RETIRE = "engine.retire"

PHASES = (EXPIRE, GROW, ADMIT, PACK, UPLOAD, DISPATCH, SYNC, RETIRE)
