"""The suite's own harness (`tests/conftest.py`): one compile cache a
run, shared by the workers and by every child process a test starts."""

import json
import os
import subprocess
import sys
import uuid

import jax

# A child that jits a function named by argv[1] and prints what JAX's
# own monitoring events say the persistent cache did.
_CHILD = """
import json, sys
import jax, jax.monitoring, jax.numpy as jnp, numpy as np
counts = {"hits": 0, "misses": 0}
def on(event, **kw):
    if event == "/jax/compilation_cache/cache_hits": counts["hits"] += 1
    if event == "/jax/compilation_cache/cache_misses": counts["misses"] += 1
jax.monitoring.register_event_listener(on)
def fn(x):
    return jnp.tanh(x) * 3.0 + 1.0
fn.__name__ = sys.argv[1]
jax.jit(fn)(np.ones((7, 5), np.float32)).block_until_ready()
print(json.dumps({"dir": jax.config.jax_compilation_cache_dir, **counts}))
"""


def _cache_dir():
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def _child(name):
    out = subprocess.run([sys.executable, "-c", _CHILD, name],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _entries(name):
    return sorted(f for f in os.listdir(_cache_dir()) if name in f)


def test_run_cache_is_set_before_jax_is_imported():
    """JAX reads the variables as it is imported: its config holds the
    run's directory only if conftest exported it first."""
    path = _cache_dir()
    assert os.path.basename(path).startswith("bigdl_tpu_test_jax_cache_")
    assert os.path.isdir(path)
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_enable_compilation_cache is True
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def _jit_a_new_function(prefix):
    """A function no run has seen: its name is in its entry's."""
    import jax.numpy as jnp

    def fn(x):
        return jnp.sin(x) + 2.0

    fn.__name__ = f"{prefix}_{uuid.uuid4().hex}"
    jax.jit(fn)(jnp.ones((3, 5))).block_until_ready()
    return fn.__name__


def test_a_new_function_leaves_an_entry_in_the_run_cache():
    assert len(_entries(_jit_a_new_function("harness_fn"))) == 1


def test_a_child_process_shares_the_run_cache_and_the_second_only_reads():
    """What the e2e tests rely on: a router replica, a chip_smoke phase
    or a tiny benchmark run compiles a program once a run."""
    name = f"harness_child_{uuid.uuid4().hex}"
    first = _child(name)
    assert first["dir"] == _cache_dir()
    assert (first["misses"], first["hits"]) == (1, 0)
    written = _entries(name)
    assert len(written) == 1
    second = _child(name)
    assert (second["misses"], second["hits"]) == (0, 1)
    assert _entries(name) == written


def test_no_compile_cache_turns_the_run_cache_off(no_compile_cache):
    """Last in the file: the fixture holds for the rest of a module."""
    assert not _entries(_jit_a_new_function("harness_cold"))
