"""What the chip bring-up added around the entry points: the one compile
cache rule, a smoke parent that never touches jax, no result without a
TPU, a benchmark that refuses a device it has no peak for, and the list
of variables that ops/ and graph/ may still read."""

import ast
import glob
import json
import os
import re
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


# ------------------------------------------- no lever in the environment --
# what ops/ and graph/ still read from the process's environment: a
# lowering is a function of the layer's arguments and of what it can
# observe. The list may only shrink (ROADMAP D1, D12): delete a name that
# has left the tree, never add one. The last is a search path, no lever.
_ENV_READS = {"SPARKNET_EPILOGUE", "SPARKNET_LRN", "SPARKNET_REMAT",
              "SPARKNET_SCAN", "SPARKNET_PRECISION",
              "SPARKNET_PYTHON_LAYER_PATH"}


def test_ops_and_graph_read_only_the_listed_variables():
    found = set()
    for sub in ("ops", "graph"):
        for path in glob.glob(os.path.join(REPO, "sparknet_tpu", sub, "**",
                                           "*.py"), recursive=True):
            with open(path) as fh:
                for n, line in enumerate(fh, 1):
                    if "environ" not in line and "getenv" not in line:
                        continue
                    named = re.findall(r"[\"']([A-Z][A-Z0-9_]+)[\"']", line)
                    # a name built at run time would hide from the list
                    assert len(named) == 1, f"{path}:{n}: {line.strip()}"
                    found.add(named[0])
    assert found == _ENV_READS, found ^ _ENV_READS


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


# ----------------------------------------------------------- benchmark --
# the two refusals of the measurement path, asked of benchmark/ (which
# the tests only run): no TPU, no result; no published peak, no default
_UNKNOWN_KIND = """
import json, os, sys, types
sys.path.insert(0, os.path.join(os.getcwd(), "benchmark"))
import jax
import flops, harness
with open(os.path.join("benchmark", "peaks.json")) as f:
    peaks = json.load(f)
assert flops.peak_for("TPU v99 imaginary", peaks) is None
assert flops.peak_for("TPU v5 lite", peaks)["bf16_flops"] > 0
jax.devices = lambda *a: [types.SimpleNamespace(
    platform="tpu", device_kind="TPU v99 imaginary")]
harness.find_device(1)
print("a device came back")
"""


def test_benchmark_refuses_a_device_kind_without_a_peak():
    res = _py(_UNKNOWN_KIND)
    assert res.returncode not in (0, None)
    assert "TPU v99 imaginary" in res.stderr and "peaks.json" in res.stderr
    assert "no result" in res.stderr and "a device came back" not in res.stdout


def test_benchmark_refuses_to_run_without_a_tpu():
    res = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"),
         "--workload", "caffenet_b1536_resident"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert not res.stdout.strip()          # no result line
