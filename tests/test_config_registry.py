"""Every registered knob has a reader.

``config._REGISTRY`` is what ``docs/faq/env_var.md`` renders and what
``check_unknown`` trusts; a knob nothing reads is an option that does
nothing.  One case a knob: its name is a string literal somewhere that
can read it, or it is one of the MXNet-1.2 names accepted and ignored
so that a reference user's environment raises no "unknown variable"
warning.  A PR that deletes a mechanism and leaves its knob fails here.
"""
import ast
import glob
import os

import pytest

from mxnet_tpu import config
from mxnet_tpu.analysis.checkers import env_knobs
from mxnet_tpu.analysis.core import iter_source_files, repo_root

# MXNet-1.2 names a reference user may have exported: registered so
# check_unknown() stays quiet, read by nothing (ROADMAP D11).
ACCEPTED_AND_IGNORED = {
    "MXNET_PROFILER_MODE": "the profiler has one mode here",
    "MXNET_EXEC_BULK_EXEC_TRAIN": "op bulking is the jit boundary",
    "MXNET_KVSTORE_BIGARRAY_BOUND": "no kvstore path splits an array "
                                    "by its size",
    "MXNET_CPU_WORKER_NTHREADS": "iterators take preprocess_threads",
    "MXNET_IMAGE_PREFETCH_BUFFER": "ImageRecordIter takes "
                                   "prefetch_buffer",
}


@pytest.fixture(scope="module")
def read_names():
    """MXNET_* string literals outside ``config.py`` in everything that
    can read a knob: the package, tools/, example/ and the root
    scripts."""
    root = repo_root()
    registry = os.path.join(root, "mxnet_tpu", "config.py")
    paths = [os.path.join(root, d)
             for d in ("mxnet_tpu", "tools", "example")]
    paths += sorted(glob.glob(os.path.join(root, "*.py")))
    names = set()
    for path in iter_source_files(paths):
        if path == registry or not path.endswith(".py"):
            continue
        with open(path, encoding="utf-8", errors="replace") as f:
            text = f.read()
        try:
            tree = ast.parse(text)
        except SyntaxError:
            continue
        names.update(env_knobs.used_names(text, tree))
    return names


@pytest.mark.parametrize("name", list(config._REGISTRY))
def test_registered_knob_has_a_reader(name, read_names):
    if name in ACCEPTED_AND_IGNORED:
        assert name not in read_names, \
            "%s has a reader now: take it off the ignored list" % name
        return
    assert name in read_names, \
        "%s is registered and nothing reads it: delete the " \
        "registration and its docs/faq/env_var.md row" % name
