"""Smoke-run every example script (examples/ doubles as user-facing
documentation, so each must stay runnable end to end)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

EXAMPLES = sorted((Path(__file__).resolve().parents[1] / "examples")
                  .glob("[0-9]*.py"))


@pytest.mark.parametrize("script", EXAMPLES, ids=lambda p: p.stem)
def test_example_runs(script):
    # tests run on the CPU backend; JAX_PLATFORMS is all it takes, and
    # nothing here starts a child to look for an accelerator
    env = dict(os.environ, WINDFLOW_EXAMPLES_SMALL="1", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=240,
                       cwd=script.parents[1])
    assert r.returncode == 0, (r.stdout, r.stderr)
    tag = f"[{script.stem.split('_')[0]}]"
    assert tag in r.stdout, r.stdout


def test_examples_exist():
    assert len(EXAMPLES) >= 7, [p.name for p in EXAMPLES]
