#!/usr/bin/env python3
"""Record the small trace kept in fedbench/testdata/: three runs of a
small jitted matmul chain and two of a second program, with the
driver loop's `fedbench:` annotations around them, on whatever device
JAX finds. Run on the chip once; the recorded file is committed.

    python3 fedbench/tests/record_trace.py <out_dir>
"""
import glob
import os
import shutil
import sys
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    @jax.jit
    def round_step(x):
        for _ in range(3):
            x = jnp.tanh(x @ x) * 0.01
        return x

    @jax.jit
    def scatter_back(x):
        return x.at[::2].set(0.0)

    x = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready(scatter_back(round_step(x)))
    tmp = os.path.join(out_dir, "tmp_trace")
    jax.profiler.start_trace(tmp)
    for i in range(3):
        with TraceAnnotation("fedbench:round"):
            with TraceAnnotation("fedbench:stage"):
                time.sleep(0.002)
            with TraceAnnotation("fedbench:api"):
                y = round_step(x)
                if i < 2:
                    y = scatter_back(y)
        jax.block_until_ready(y)
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(
        tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    dst = os.path.join(out_dir, "small.xplane.pb")
    shutil.copyfile(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(dst, os.path.getsize(dst), jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
