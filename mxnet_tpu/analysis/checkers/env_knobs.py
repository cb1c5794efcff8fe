"""env-knob-drift — every ``MXNET_*`` knob the code reads must be
registered in ``config.py`` and documented in ``docs/faq/env_var.md``.

Generalizes the three hand-rolled drift guards that used to live in
``tests/test_op_sweep.py`` / ``tests/test_serving.py`` /
``tests/test_predictor_config.py`` (those tests are now thin wrappers
over :func:`drift_report`): the registry (``config.register_env``) is
parsed STATICALLY from ``config.py``'s AST — the tree must be lintable
even when it does not import — and the doc surface is the env_var.md
table.  Two directions are enforced:

- a ``MXNET_*`` string literal anywhere in package source (the name
  that eventually reaches ``os.environ`` / ``os.getenv`` /
  ``config.get``) that is not registered, or registered but not
  documented, is flagged at its use site;
- a ``register_env`` name with no env_var.md row is flagged at its
  registration site (the old test_predictor_config guard).

Docstrings are skipped — they cite the reference framework's knobs and
C++ macro names (``MXNET_REGISTER_IO_ITER``) that are not knobs here.
"""
from __future__ import annotations

import ast
import os
import re

from ..core import Checker, Finding, register

__all__ = ["EnvKnobChecker", "drift_report", "registered_names",
           "documented_names"]

_NAME_RE = re.compile(r"MXNET_[A-Z0-9_]*")


def _strip(token):
    """Normalize a matched token: docstring wildcards like
    ``MXNET_TELEMETRY*`` arrive as ``MXNET_TELEMETRY_`` here."""
    return token.rstrip("_")


def registered_names(config_path):
    """Names declared via ``register_env("NAME", ...)`` — read from the
    AST, not by importing config (the tree may be broken)."""
    with open(config_path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    names = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "register_env"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            names[node.args[0].value] = node.lineno
    return names


def documented_names(doc_path):
    with open(doc_path, encoding="utf-8") as f:
        text = f.read()
    return {_strip(m) for m in _NAME_RE.findall(text)} - {"MXNET"}


def _docstring_lines(tree):
    """Line ranges of module/class/function docstrings, to exclude."""
    spans = []
    nodes = [tree] + [n for n in ast.walk(tree)
                      if isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef,
                                        ast.ClassDef))]
    for node in nodes:
        body = getattr(node, "body", [])
        if (body and isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)):
            doc = body[0].value
            spans.append((doc.lineno, doc.end_lineno or doc.lineno))
    covered = set()
    for lo, hi in spans:
        covered.update(range(lo, hi + 1))
    return covered


def used_names(text, tree):
    """``{name: first_line}`` of MXNET_* tokens inside non-docstring
    string literals of one source file."""
    if tree is None:
        return {}
    doc_lines = _docstring_lines(tree)
    used = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Constant)
                and isinstance(node.value, str)):
            continue
        if node.lineno in doc_lines:
            continue
        for m in _NAME_RE.findall(node.value):
            name = _strip(m)
            if name and name != "MXNET" and name not in used:
                used[name] = node.lineno
    return used


@register
class EnvKnobChecker(Checker):
    rule = "env-knob-drift"
    severity = "error"
    suffixes = (".py",)

    def _tables(self, ctx):
        key = "env-knob-tables"
        if key not in ctx.memo:
            config_path = os.path.join(ctx.root, "mxnet_tpu", "config.py")
            doc_path = os.path.join(ctx.root, "docs", "faq", "env_var.md")
            registered = (registered_names(config_path)
                          if os.path.exists(config_path) else {})
            documented = (documented_names(doc_path)
                          if os.path.exists(doc_path) else set())
            ctx.memo[key] = (registered, documented)
        return ctx.memo[key]

    def check(self, path, relpath, text, tree, ctx):
        registered, documented = self._tables(ctx)
        out = []
        is_config = relpath.replace("\\", "/").endswith("mxnet_tpu/config.py")
        if is_config:
            # registration site direction: every registered knob needs
            # an env_var.md row
            for name, line in sorted(registered.items()):
                if name not in documented:
                    out.append(Finding(
                        self.rule, self.severity, relpath, line,
                        "registered env var %s has no docs/faq/env_var.md "
                        "row" % name, symbol="register_env"))
            return out
        for name, line in sorted(used_names(text, tree).items()):
            if name not in registered:
                out.append(Finding(
                    self.rule, self.severity, relpath, line,
                    "%s is read here but never register_env'd in "
                    "config.py (typo or undeclared knob)" % name))
            elif name not in documented:
                out.append(Finding(
                    self.rule, self.severity, relpath, line,
                    "%s is registered but missing from "
                    "docs/faq/env_var.md" % name))
        return out


def drift_report(prefix=None, root=None):
    """One-call report for the test-suite wrappers.

    Returns ``{"used": {...}, "unregistered": [...], "undocumented":
    [...], "registered_undocumented": [...]}`` over the whole package.
    ``prefix`` (a str or tuple) restricts the *used* directions to
    matching names — each legacy guard scoped itself to its own knob
    family."""
    from ..core import repo_root, iter_source_files
    root = root or repo_root()
    config_path = os.path.join(root, "mxnet_tpu", "config.py")
    doc_path = os.path.join(root, "docs", "faq", "env_var.md")
    registered = registered_names(config_path)
    documented = documented_names(doc_path)
    used = {}
    for path in iter_source_files([os.path.join(root, "mxnet_tpu")]):
        if not path.endswith(".py"):
            continue
        with open(path, encoding="utf-8", errors="replace") as f:
            text = f.read()
        try:
            tree = ast.parse(text)
        except SyntaxError:
            continue
        rel = os.path.relpath(path, root)
        for name, line in used_names(text, tree).items():
            used.setdefault(name, (rel, line))
    if prefix is not None:
        prefixes = (prefix,) if isinstance(prefix, str) else tuple(prefix)
        scoped = {n: w for n, w in used.items() if n.startswith(prefixes)}
    else:
        scoped = used
    return {
        "used": scoped,
        "unregistered": sorted(n for n in scoped if n not in registered),
        "undocumented": sorted(n for n in scoped if n not in documented),
        "registered_undocumented": sorted(
            n for n in registered if n not in documented),
    }
