#!/usr/bin/env python3
"""Record the scoped trace kept in fedbench/testdata/: four "rounds" of
a small jitted `round_step` whose ops lie under the program's layer
scopes (`commefficient_tpu.scopes.scope`: `fwdbwd` through a
`jax.grad`, so its ops carry `jvp(...)` and `transpose(jvp(...))`
wrappers; `select`; `server_state` with an `encode` nested inside it,
which stays `server_state`; one op under no scope) and of a
`scatter_back`, driven under the program's TRACE spans (`round` >
`stage`, `dispatch`, `collect` > `device_wait`), each of which also
opens a `fed:<stage>` TraceAnnotation, with a pause under no span
between rounds. Run on the chip once; the recorded file is committed.

    python3 fedbench/tests/record_scoped_trace.py <out_dir>
"""
import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from commefficient_tpu.scopes import scope
    from commefficient_tpu.telemetry.trace import TRACE

    def loss(w, x):
        with scope("fwdbwd"):
            return jnp.sum(jnp.tanh(x @ w) ** 2)

    @jax.jit
    def round_step(w, x):
        g = jax.grad(loss)(w, x)
        with scope("select"):
            thr = jnp.sort(jnp.abs(g).reshape(-1))[-4096]
            kept = jnp.where(jnp.abs(g) >= thr, g, 0.0)
        with scope("server_state"):
            with scope("encode"):
                table = kept.reshape(8, -1).sum(axis=0)
            w = w - 0.1 * kept
        return w, jnp.cumsum(table)      # the cumsum: under no scope

    @jax.jit
    def scatter_back(w):
        with scope("scatter_back"):
            return w.at[::2].set(0.0)

    w = jnp.ones((1024, 1024), jnp.float32) * 0.01
    x = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready(scatter_back(round_step(w, x)[0]))
    tmp = os.path.join(out_dir, "tmp_trace")
    TRACE.enable()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=options)
    for i in range(4):
        with TRACE.span("round", round=i):
            with TRACE.span("stage"):
                time.sleep(0.002)
            with TRACE.span("dispatch"):
                w, t = round_step(w, x)
                w = scatter_back(w)
            with TRACE.span("collect"):
                with TRACE.span("device_wait"):
                    jax.block_until_ready((w, t))
        time.sleep(0.001)                # under no span
    jax.profiler.stop_trace()
    TRACE.disable()
    src = sorted(glob.glob(os.path.join(
        tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    dst = os.path.join(out_dir, "scoped.xplane.pb")
    shutil.copyfile(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(dst, os.path.getsize(dst), jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
