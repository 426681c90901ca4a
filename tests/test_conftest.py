"""tests/conftest.py's one rule with a behaviour of its own: a test that
needs the reference checkout skips, with the reason, where the checkout is
absent — and nothing else that is missing turns into a skip."""

import os
import shutil
import subprocess
import sys

from conftest import REFERENCE

HERE = os.path.dirname(os.path.abspath(__file__))

_PROBE = '''
from conftest import REFERENCE, reference_path

def test_spells_the_path_itself():
    open(REFERENCE + "/caffe/no_such.prototxt")

def test_asks_the_helper():
    open(reference_path("caffe", "no_such.prototxt"))

def test_misses_a_file_of_its_own():
    open("/no/such/directory/of/this/test")
'''


def test_absent_reference_skips_and_other_missing_files_fail(tmp_path):
    shutil.copy(os.path.join(HERE, "conftest.py"), tmp_path / "conftest.py")
    (tmp_path / "test_probe.py").write_text(_PROBE)
    res = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rs", "-p",
         "no:cacheprovider", "-p", "no:xdist", "--rootdir", str(tmp_path),
         str(tmp_path / "test_probe.py")],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    out = res.stdout
    if os.path.isdir(REFERENCE):
        # the checkout is here: a file missing from it is a failure
        assert "3 failed" in out, out
    else:
        assert "1 failed" in out and "2 skipped" in out, out
        assert f"reference checkout absent: {REFERENCE}" in out, out
