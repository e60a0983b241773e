"""Docs <-> CLI consistency: every flag named in README/docs must exist in
an argparse parser, and every user-facing parser flag must be documented.

The parsers are collected in a subprocess because importing
``repro.launch.dryrun`` mutates ``XLA_FLAGS`` at module import (it must
precede jax backend init for the 512-device dry-run) — the main pytest
process keeps its environment untouched.
"""
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOC_FILES = ["README.md", "docs/architecture.md", "docs/transforms.md",
             "docs/benchmarks.md"]

# flags that belong to external tools (XLA, ruff), not to our parsers
EXTERNAL_PREFIXES = ("--xla", "--select")

_COLLECT = r"""
import json
from repro.launch.train import build_parser as train_parser
from repro.launch.dryrun import build_parser as dryrun_parser
from repro.launch.serve import build_parser as serve_parser
from benchmarks.run import build_parser as bench_parser
from benchmarks.check_regression import build_parser as regression_parser
from repro.kernels.autotune import build_parser as autotune_parser
from chip_smoke import build_parser as chip_smoke_parser

out = {}
for name, build in [("train", train_parser), ("dryrun", dryrun_parser),
                    ("serve", serve_parser), ("benchmarks", bench_parser),
                    ("check_regression", regression_parser),
                    ("autotune", autotune_parser),
                    ("chip_smoke", chip_smoke_parser)]:
    flags = set()
    for action in build()._actions:
        flags.update(o for o in action.option_strings if o.startswith("--"))
    flags.discard("--help")
    out[name] = sorted(flags)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def parser_flags() -> dict[str, set[str]]:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    res = subprocess.run([sys.executable, "-c", _COLLECT], capture_output=True,
                         text=True, env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-3000:]
    raw = json.loads(res.stdout.strip().splitlines()[-1])
    return {k: set(v) for k, v in raw.items()}


def _doc_flags() -> dict[str, set[str]]:
    """--flag tokens per doc file (= signed both in prose and code blocks)."""
    found = {}
    for rel in DOC_FILES:
        path = os.path.join(REPO, rel)
        assert os.path.exists(path), f"{rel} is missing"
        with open(path) as f:
            text = f.read()
        flags = set(re.findall(r"(?<![\w-])--[a-z][a-z0-9-]*", text))
        found[rel] = {f for f in flags
                      if not f.startswith(EXTERNAL_PREFIXES)}
    return found


def test_every_documented_flag_exists(parser_flags):
    """No doc may name a CLI flag that no parser defines (docs can't rot)."""
    known = set().union(*parser_flags.values())
    for rel, flags in _doc_flags().items():
        unknown = flags - known
        assert not unknown, (
            f"{rel} names flags missing from every argparse parser: "
            f"{sorted(unknown)}")


def test_every_user_facing_flag_is_documented(parser_flags):
    """Every flag of the user-facing CLIs (train / dryrun / serve /
    benchmark runner) must appear in README or docs/."""
    documented = set().union(*_doc_flags().values())
    for cli, flags in parser_flags.items():
        missing = flags - documented
        assert not missing, (
            f"{cli} CLI flags undocumented in README/docs: {sorted(missing)}")


def test_reference_losses_documented():
    """The behavior-preservation reference values must match the pinned
    parity-test constants wherever they are quoted."""
    from test_parity import REFERENCE

    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    for inner, loss in REFERENCE.items():
        assert f"{loss:.4f}" in readme, (
            f"README does not quote the pinned {inner} reference loss {loss}")
