"""Record the small TPU profiler trace that ``test_reduce.py`` reads.

Run on a machine with a TPU, from the checkout root:

    python3 bench/tests/make_trace_fixture.py

Three calls of a small jitted scan (``fixture_step``), each inside a
``bench.dispatch`` span; inside the second, the host sleeps 20 ms in a
``fixture.host_sleep`` span between two device calls, which leaves an
idle gap of known cause.  The device planes' op and module lines and
the host thread that holds the spans are kept, as JSON events.
"""
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "bench", "tests", "fixtures", "tpu_trace.json")


def main() -> int:
    import jax
    import jax.numpy as jnp

    from bench import trace

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")

    @jax.jit
    def fixture_step(x):
        def body(c, _):
            return jnp.tanh(c @ c) * 0.5, ()
        return jax.lax.scan(body, x, None, length=8)[0]

    x = jnp.ones((512, 512), jnp.float32)
    fixture_step(x).block_until_ready()
    logdir = os.path.join(ROOT, ".bench_cache", "fixture_profile")
    shutil.rmtree(logdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(logdir, profiler_options=opts):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = fixture_step(x).block_until_ready()
                if i == 1:
                    with jax.profiler.TraceAnnotation("fixture.host_sleep"):
                        time.sleep(0.02)
                    y = fixture_step(y).block_until_ready()
                jax.device_get(y)
    events = trace.read_xplane(logdir, "bench.dispatch", keep_ops=True)[0]
    shutil.rmtree(logdir, ignore_errors=True)
    host = {(e.plane, e.line) for e in events if e.name == "bench.dispatch"}
    keep = [e for e in events
            if (trace.DEVICE_PLANE.fullmatch(e.plane)
                and e.line in (trace.OPS_LINE, trace.MODULES_LINE))
            or (e.plane, e.line) in host]
    lo = min(e.start for e in keep)
    keep = [e._replace(start=e.start - lo) for e in keep]
    trace.save(keep, OUT)
    lines = sorted({(e.plane, e.line) for e in events})
    print(f"kept {len(keep)} of {len(events)} events; planes and lines: "
          f"{lines}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
