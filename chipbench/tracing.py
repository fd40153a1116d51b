"""The profiler slice of a ``--trace 1`` run, and the harness's host spans.

The slice follows the measured window: the same loop goes on for
``seconds`` more under ``jax.profiler``, so the window's own numbers are
taken with the profiler off.  Host spans are ``TraceAnnotation``s named
``cb:<what>``; the profiler puts them on the device trace's clock and
``trace_reduce`` attributes the device's idle gaps to them."""
import contextlib
import glob
import os
import shutil
import tempfile

import trace_reduce


def span(name):
    import jax
    return jax.profiler.TraceAnnotation("cb:" + name)


def no_span(name):
    return contextlib.nullcontext()


class Slice:
    """``with Slice(keep) as s: ...`` traces the body; afterwards
    ``s.result`` is the reducer's output (None where no operation ran on a
    device).  The trace goes to a directory under TMPDIR that is removed
    again; ``keep`` names a directory to copy the .xplane.pb into."""

    def __init__(self, keep=None):
        self.keep = keep
        self.result = None

    def __enter__(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        # the Python tracer would record every call the host makes and slow
        # the host it is there to watch: off; TraceMe spans stay on
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        import jax
        try:
            jax.profiler.stop_trace()
            if exc[0] is None:
                found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                  recursive=True)
                if found:
                    if self.keep:
                        os.makedirs(self.keep, exist_ok=True)
                        shutil.copy(found[0], self.keep)
                    self.result = trace_reduce.reduce(
                        trace_reduce.read_xplane(found[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return False


def traced_slice(cell, traffic, pump):
    """The window's own loop ``pump(seconds, span)`` once more under the
    profiler, for the traffic file's ``trace_seconds`` (or the length given
    on the command line); returns the reducer's output."""
    with Slice(cell.get("keep_trace")) as s:
        pump(cell.get("trace_seconds") or traffic["trace_seconds"], span)
    return s.result
