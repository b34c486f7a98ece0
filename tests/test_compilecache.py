"""Where the persistent compile cache lives (utils/compilecache.py).

The directory is placed from outside: JAX's own setting wins and the
program sets none; unset, it is <checkout>/.jax_cache whatever the cwd
and $HOME; a default directory that cannot be used is an error, not a
silent uncached run.  Each case needs a fresh interpreter (enable() runs
at kubernetes_tpu.ops import and JAX reads its environment once), so the
three run as concurrent subprocesses behind one module fixture.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SPY = """
import json, jax
updates = []
_orig = jax.config.update
def _spy(name, value):
    updates.append(name)
    return _orig(name, value)
jax.config.update = _spy
"""

_IMPORT_OPS = _SPY + """
import kubernetes_tpu.ops
print(json.dumps({"dir": jax.config.jax_compilation_cache_dir,
                  "updates": updates}))
"""

_UNUSABLE = _SPY + """
import sys
from kubernetes_tpu.utils import compilecache
compilecache.CHECKOUT_CACHE_DIR = sys.argv[1]
try:
    compilecache.enable()
    err = None
except OSError as e:
    err = str(e)
print(json.dumps({"dir": jax.config.jax_compilation_cache_dir,
                  "updates": updates, "error": err}))
"""


@pytest.fixture(scope="module")
def cases(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("compilecache")
    home = tmp / "home"
    home.mkdir()
    blocker = tmp / "a-file"
    blocker.write_text("not a directory")
    outside = str(tmp / "placed-from-outside")
    base = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR",
                     "JAX_ENABLE_COMPILATION_CACHE")
    }
    base.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, HOME=str(home))
    specs = {
        "outside": (_IMPORT_OPS, [],
                    dict(base, JAX_COMPILATION_CACHE_DIR=outside)),
        "default": (_IMPORT_OPS, [], base),
        "unusable": (_UNUSABLE, [str(blocker / ".jax_cache")], base),
    }
    procs = {
        name: subprocess.Popen(
            [sys.executable, "-c", code, *argv], cwd=str(tmp), env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for name, (code, argv, env) in specs.items()
    }
    out = {}
    for name, proc in procs.items():
        stdout, stderr = proc.communicate(timeout=120)
        assert proc.returncode == 0, stderr[-2000:]
        out[name] = json.loads(stdout.strip().splitlines()[-1])
    out["paths"] = {"outside": outside, "home": str(home),
                    "blocker": str(blocker)}
    return out


def test_outside_directory_is_kept_and_none_is_set_in_code(cases):
    got = cases["outside"]
    assert got["dir"] == cases["paths"]["outside"]
    assert "jax_compilation_cache_dir" not in got["updates"]
    # the zeroed gates still apply: every executable is cached
    assert "jax_persistent_cache_min_compile_time_secs" in got["updates"]
    assert "jax_persistent_cache_min_entry_size_bytes" in got["updates"]
    # a directory given from outside is JAX's to create and validate
    assert not os.path.exists(cases["paths"]["outside"])


def test_default_is_the_checkout_whatever_cwd_and_home(cases):
    got = cases["default"]
    assert got["dir"] == os.path.join(ROOT, ".jax_cache")
    assert os.listdir(cases["paths"]["home"]) == []


def test_unusable_default_directory_is_an_error(cases):
    got = cases["unusable"]
    assert got["error"] is not None
    assert cases["paths"]["blocker"] in got["error"]
    assert "JAX_COMPILATION_CACHE_DIR" in got["error"]
    assert got["dir"] is None
    assert "jax_compilation_cache_dir" not in got["updates"]
