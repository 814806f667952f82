"""Persistent-compilation-cache evidence: a jitted program run in two
fresh subprocesses sharing one cache dir hits the disk cache the
second time, and the directory is the one utils/cache.py documents —
JAX_COMPILATION_CACHE_DIR where it is set (left to JAX, nothing set in
code), else the fixed `.jax_cache/` of the checkout whatever the
working directory."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os, sys, time
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.environ["REPO_ROOT"])
    from commefficient_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )
    path = enable_persistent_compilation_cache(
        os.environ.get("CACHE_DIR") or None)
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        # UNROLLED chain of distinct fusions: crosses the production
        # 1 s min-compile-time persistence floor on CPU (a scanned
        # body compiles once and stays under it)
        c = x
        for i in range(300):
            c = jnp.tanh(c @ c.T) @ c + jnp.sin(c) * (i + 1)
        return c.sum()

    hits = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: hits.append(name)
        if name == "/jax/compilation_cache/cache_hits" else None)
    t0 = time.time()
    f.lower(jnp.ones((150, 150))).compile()   # the compile alone
    print(f"compile_s={time.time() - t0:.3f}")
    print(f"hits={len(hits)}")
    print(f"entries={len(os.listdir(path))}")
    print(f"path={path}")
    print(f"configured={jax.config.jax_compilation_cache_dir}")
""")

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_script(env, cwd, script=SCRIPT):
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=300,
                       env=env, cwd=cwd)
    assert r.returncode == 0, r.stderr[-2000:]
    vals = dict(line.split("=", 1) for line in r.stdout.split()
                if "=" in line)
    vals["stderr"] = r.stderr[-1500:]
    return vals


def test_second_process_hits_disk_cache(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "CACHE_DIR": str(tmp_path / "xla"), "REPO_ROOT": REPO_ROOT}
    cold = _run_script(env, str(tmp_path))
    warm = _run_script(env, str(tmp_path))
    assert int(cold["entries"]) > 0, \
        f"first run should have written a cache entry: {cold}"
    # the cold run compiles and persists; the warm run loads the
    # executable from disk (JAX's own cache-hit event; the seconds are
    # printed for the reader, a loaded host makes them a poor gate)
    assert int(cold["hits"]) == 0 and int(warm["hits"]) >= 1, (cold, warm)
    assert int(warm["entries"]) == int(cold["entries"])


def test_env_dir_is_left_to_jax(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the function sets no directory
    of its own and the entries land where the variable says."""
    where = tmp_path / "from_env"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "REPO_ROOT": REPO_ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(where)}
    env.pop("CACHE_DIR", None)
    # where the entry lands is the subject, not the production 1 s
    # floor (the two-process test above holds that): persist it all
    script = SCRIPT.replace(
        "import jax.numpy as jnp",
        "jax.config.update('jax_persistent_cache_min_compile_time_secs'"
        ", 0.0)\nimport jax.numpy as jnp")
    vals = _run_script(env, str(tmp_path), script)
    assert vals["path"] == vals["configured"] == str(where)
    assert int(vals["entries"]) > 0 and os.listdir(where)
    assert not os.path.exists(tmp_path / ".jax_cache")


def test_default_dir_is_fixed_under_the_checkout(tmp_path):
    """No variable, no argument: `.jax_cache/` of the checkout, the
    same from two working directories (the path is part of the
    cache's key, so a directory that moves never hits). Only the path
    is resolved here, nothing is compiled into the checkout."""
    script = SCRIPT.split("import jax.numpy")[0] + (
        "print(f'path={path}')\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "REPO_ROOT": REPO_ROOT}
    env.pop("CACHE_DIR", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    other = tmp_path / "elsewhere"
    other.mkdir()
    paths = {_run_script(env, cwd, script)["path"]
             for cwd in (REPO_ROOT, str(other))}
    assert paths == {os.path.join(REPO_ROOT, ".jax_cache")}


KEY_SCRIPT = textwrap.dedent("""
    import os, re, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.environ["REPO_ROOT"])
    sys.path.insert(0, os.environ["PROG_DIR"])
    from commefficient_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )
    enable_persistent_compilation_cache(os.environ["CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import jax.numpy as jnp
    import prog
    hits = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: hits.append(name)
        if name == "/jax/compilation_cache/cache_hits" else None)
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    text = prog.f.lower(x).compile().as_text()
    print(f"hits={len(hits)}")
    print("scoped=%d" % sum(
        "fed_" in n for n in re.findall(r'op_name="([^"]*)"', text)))
""")

PROG = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from commefficient_tpu.scopes import scope

    @jax.jit
    def f(x):
        with scope("SCOPE"):
            return jnp.tanh(x @ x).sum()
""")


def test_cache_key_holds_the_layer_names(tmp_path):
    """The layer scopes live in op metadata and a device trace reads
    them back, so the cache is keyed with it: the same program under
    another scope name compiles anew (JAX's default key strips names
    and would hand back the old executable with the old names), the
    same program again hits, and the compiled ops carry the scope in
    their `op_name` with the cache's settings on."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "REPO_ROOT": REPO_ROOT,
           "CACHE_DIR": str(tmp_path / "xla"), "PROG_DIR": str(tmp_path)}

    def run(scope_name):
        (tmp_path / "prog.py").write_text(
            PROG.replace("SCOPE", scope_name))
        vals = _run_script(env, str(tmp_path), KEY_SCRIPT)
        assert int(vals["scoped"]) > 0
        return int(vals["hits"])

    assert run("encode") == 0            # cold
    assert run("encode") == 1            # the same program: hit
    assert run("select") == 0            # renamed layer: miss
    assert run("select") == 1
