"""Named host spans of the serving path, written into the JAX profiler's
trace.

``span(name, **stats)`` is ``jax.profiler.TraceAnnotation``: while a
profiler trace is being taken (``jax.profiler.trace`` /
``start_trace``) each span becomes a host event on the same timeline as
the device's operations, so a gap in which the device sat idle can be
laid against what the host was doing; with no trace running a span costs
about a microsecond and records nothing.  The profiler being active is
the only switch.

Spans open once per scheduling window or per phase of one, never once
per job.  Every name starts with ``elis.``; the dotted prefix is the
layer (``elis.sched.*`` frontend and scheduler, ``elis.predict*`` length
predictor, ``elis.engine.*`` engine).
"""
from jax.profiler import TraceAnnotation as span

#: every span name the program emits
NAMES = (
    # frontend and scheduler (core/frontend.py)
    "elis.sched.arrival",       # one arrival placed on a node
    "elis.sched.form_batch",    # choosing the next window's batch
    "elis.sched.score",         # scoring the pool (runs the predictor)
    "elis.sched.preempt",       # victims chosen, evicted or offloaded
    "elis.sched.fill",          # free slots filled from the waiters
    "elis.sched.apply",         # a window's tokens, finishes, evictions
    # length predictor (core/predictor.py)
    "elis.predict",             # one batched prediction of a pool
    "elis.predict.pad",         # inputs clipped and padded on the host
    "elis.predict.sync",        # dispatch and read-back of the encoder
    "elis.predict.post",        # the LengthPredictions built
    # engine (engine/engine.py)
    "elis.engine.execute",      # one window (stat ``batch``)
    "elis.engine.restore",      # host-stashed caches swapped back in
    "elis.engine.offload",      # one victim's cache stashed on the host
    "elis.engine.admit",        # new jobs given slots by one prefill
    "elis.engine.alloc",        # the admission's fresh batch cache
    "elis.engine.prefill",      # the prefill program dispatched
    "elis.engine.scatter",      # sub-cache rows written into the slots
    "elis.engine.chunk",        # one chunk of a chunked prefill
    "elis.engine.decode",       # the window's decode, end to end
    "elis.engine.gather",       # scheduled slots gathered to a sub-cache
    "elis.engine.decode_run",   # the decode-window program dispatched
    "elis.engine.emit",         # per-job tokens cut to EOS and budget
    "elis.engine.len_write",    # the slots' lengths written back
    "elis.engine.sync",         # one device-to-host read
)

__all__ = ["NAMES", "span"]
