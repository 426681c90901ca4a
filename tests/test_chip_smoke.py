"""What the chip bring-up added around the entry points: the one compile
cache rule, a smoke parent that never touches jax, no result without a
TPU, and a benchmark that refuses a device it has no peak for."""

import ast
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _py(code, env_drop=(), **env_set):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update(env_set)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)


# ------------------------------------------------------- compile cache --
# spelled in two halves so that a search of the tree for the setting finds
# the helper and nothing else, this file included
_SETTING = "jax_compilation_" + "cache_dir"
_CACHE_PROBE = f"""
import jax
from sparknet_tpu.utils.compile_cache import configure_compile_cache
first = configure_compile_cache()
second = configure_compile_cache()
assert first == second, (first, second)
print("CACHE", first, getattr(jax.config, "{_SETTING}"))
"""
# the variables the suite itself runs under, which would decide the case
_SUITE_ENV = ("JAX_PLATFORMS", "JAX_ENABLE_COMPILATION_CACHE",
              "JAX_COMPILATION_CACHE_DIR")


def _cache_probe(**env_set):
    res = _py(_CACHE_PROBE, env_drop=_SUITE_ENV, **env_set)
    assert res.returncode == 0, res.stderr
    line = next(ln for ln in res.stdout.splitlines()
                if ln.startswith("CACHE "))
    return line.split()[1:]


def test_cache_dir_from_the_variable_is_left_alone(tmp_path):
    where = str(tmp_path / "placed_from_outside")
    returned, configured = _cache_probe(JAX_COMPILATION_CACHE_DIR=where)
    # jax read the variable itself; the helper named no directory
    assert returned == configured == where
    assert not os.path.exists(where)       # nothing created, nothing moved


def test_cache_dir_unset_is_one_fixed_path_in_the_checkout():
    a = _cache_probe()
    b = _cache_probe()                     # a second process
    assert a == b
    assert a[0] == a[1] == os.path.join(REPO, ".jax_cache")
    ignored = subprocess.run(["git", "check-ignore", "-q", ".jax_cache/x"],
                             cwd=REPO)
    if os.path.isdir(os.path.join(REPO, ".git")):
        assert ignored.returncode == 0, ".jax_cache must be git-ignored"


@pytest.mark.parametrize("env", [{"JAX_PLATFORMS": "cpu"},
                                 {"JAX_ENABLE_COMPILATION_CACHE": "false"}])
def test_no_cache_dir_for_cpu_runs_and_tests(env):
    assert _cache_probe(**env) == ["None", "None"]


def test_only_the_helper_names_a_cache_dir():
    # the files git holds; a tree without git holds nothing else anyway
    listed = subprocess.run(["git", "ls-files", "*.py", "*.sh"], cwd=REPO,
                            capture_output=True, text=True)
    if listed.returncode == 0 and listed.stdout.strip():
        files = listed.stdout.split()
    else:
        files = [os.path.relpath(os.path.join(root, f), REPO)
                 for root, _, names in os.walk(REPO) for f in names
                 if f.endswith((".py", ".sh"))]
    hits = []
    for rel in files:
        p = os.path.join(REPO, rel)
        if os.path.isfile(p):
            with open(p, errors="replace") as fh:
                if _SETTING in fh.read():
                    hits.append(rel)
    assert hits == ["sparknet_tpu/utils/compile_cache.py"]


# ---------------------------------------------------------- chip_smoke --
def test_smoke_parent_module_imports_only_stdlib():
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        tree = ast.parse(f.read())
    top = set()
    for node in tree.body:
        if isinstance(node, ast.Import):
            top.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            top.add((node.module or "").split(".")[0])
    assert top <= set(sys.stdlib_module_names), top


_PARENT_PROBE = """
import json, sys, tempfile
import chip_smoke
chip_smoke.WORK = tempfile.mkdtemp()    # keep off the checkout's own logs
chip_smoke.LOGS = tempfile.mkdtemp()

def clean():
    bad = [m for m in sys.modules
           if m.split(".")[0] in ("jax", "jaxlib", "sparknet_tpu")]
    assert not bad, bad

launched = []
def fake_run_child(cmd, log_name, env, timeout):
    clean()                     # at every launch of a child
    launched.append(log_name)
    if log_name == "probe":
        return ('CHILD_JSON ' + json.dumps(
            {"platform": "tpu", "kind": "fake", "count": 1})), 0.0
    raise chip_smoke.Failed("the test stops after the first phase starts")

chip_smoke.run_child = fake_run_child
rc = chip_smoke.main([])
clean()
print("LAUNCHED", launched, "RC", rc)
"""


def test_smoke_parent_never_imports_jax():
    res = _py(_PARENT_PROBE)
    assert res.returncode == 0, res.stderr
    assert "LAUNCHED ['probe', 'cnn'] RC 1" in res.stdout
    assert '"ok": true' not in res.stdout


def test_smoke_on_cpu_exits_nonzero_without_a_result():
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no TPU" in res.stderr


# --------------------------------------------------------------- bench --
_BENCH_UNKNOWN_KIND = """
import sys, types
import bench
bench.bench_device = lambda: types.SimpleNamespace(
    platform="tpu", device_kind="TPU v99 imaginary")
sys.argv = ["bench.py", "--metrics", ""]
sys.exit(bench.main())
"""


def test_bench_refuses_a_device_kind_without_a_peak():
    res = _py(_BENCH_UNKNOWN_KIND)
    assert res.returncode not in (0, None)
    assert "TPU v99 imaginary" in res.stderr and "_PEAK" in res.stderr
    assert "#BENCH" not in res.stderr      # no row was measured


def test_bench_refuses_to_run_without_a_tpu():
    res = subprocess.run([sys.executable, "bench.py", "--metrics", ""],
                         cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert not res.stdout.strip()          # no headline JSON either
