"""Record ``prep_job.xplane.pb``: a small CPU profile that carries the
program's own span annotations, for the tests of the metric readers.

    JAX_PLATFORMS=cpu python benchmark/tests/data/make_prep_job.py

Inside a ``bench.job`` annotation, three rounds of: a recorded
``bqsr.observe.prep`` span that sleeps (the host prepares while the
device idles), a jitted product, then a prep span opened while an
asynchronously dispatched product still runs.  One prep span before the
job opens lies outside it."""

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import jax.profiler as jp

    from adam_tpu.utils import telemetry as tele

    tr = tele.Tracer(recording=True)
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()  # compile outside the profile
    tmp = tempfile.mkdtemp()
    opts = jp.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jp.start_trace(tmp, profiler_options=opts)
    with tr.span(tele.SPAN_BQSR_OBSERVE_PREP):
        time.sleep(0.01)
    with jp.TraceAnnotation("bench.job"):
        for _ in range(3):
            with tr.span(tele.SPAN_BQSR_OBSERVE_PREP):
                time.sleep(0.02)
            f(x).block_until_ready()
            y = f(x)
            with tr.span(tele.SPAN_BQSR_OBSERVE_PREP):
                time.sleep(0.005)
            y.block_until_ready()
            time.sleep(0.01)
    jp.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    shutil.copy(path, os.path.join(HERE, "prep_job.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
