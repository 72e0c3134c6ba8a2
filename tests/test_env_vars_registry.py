"""Env-var drift guard: every MX_*/MXNET_* variable read anywhere in
mxnet_tpu/ or tools/ must be registered in mxnet_tpu.env_vars.ENV_VARS.

The registry is the single answer to "is MXNET_X supported here?" — a
variable consumed at some use-site but absent from the table silently
drifts out of the documentation, out of `env_vars.check()`'s
set-but-ineffective warnings, and out of docs/OBSERVABILITY.md's knob
list.

Since the mxlint PR this test delegates to the `env-unregistered` rule
(tools/mxlint.py): same convention — a quoted MX_/MXNET_ name is a
use-site — but at the AST level, so docstring mentions like "MX_FOO" no
longer false-positive the way the old quoted-string regex could, and the
finding carries the offending file.  Adding an env read without
registering it still fails tier-1 immediately.
"""
import importlib.util
import os
import re

from mxnet_tpu import env_vars

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "mxlint", os.path.join(_REPO, "tools", "mxlint.py"))
_mxlint = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mxlint)

_NAME_IN_MSG = re.compile(r"env var '(MX(?:NET)?_[A-Z0-9_]+)'")


def _scan(registry):
    """name -> sorted files, for every AST-level use-site the
    env-unregistered rule reports against `registry`."""
    findings, _stats = _mxlint.run_lint(
        ["mxnet_tpu", "tools"], root=_REPO, rules=["env-unregistered"],
        env_registry=registry)
    sites = {}
    # meta rules (bad-suppression, syntax-error) always run; their
    # findings are someone else's problem (test_lint's full-tree gate) —
    # only env-unregistered messages carry a var name to parse
    for f in findings:
        if f.rule != "env-unregistered":
            continue
        m = _NAME_IN_MSG.search(f.message)
        assert m, f"unparseable env-unregistered message: {f.message}"
        sites.setdefault(m.group(1), set()).add(f.path)
    return sites


def test_every_env_var_in_tree_is_registered():
    # one scan with an EMPTY registry reports every use-site; the missing
    # set is then a plain membership check against ENV_VARS.  Zero hits
    # means the scanner (or the tree layout) broke.
    sites = _scan(registry=set())
    assert sites, "scanner found no env vars at all — rule or layout broke"
    missing = {name: sorted(files) for name, files in sorted(sites.items())
               if name not in env_vars.ENV_VARS}
    assert not missing, (
        "env vars read in the tree but not registered in "
        "mxnet_tpu/env_vars.py ENV_VARS (add an entry with disposition + "
        f"use-site): {missing}")


def test_registry_covers_telemetry_knobs():
    # the observability layer's knobs must stay documented
    for name in ("MX_TELEMETRY_DIR", "MX_TELEMETRY_FLUSH_SEC",
                 "MX_HEARTBEAT_SEC", "MX_TELEMETRY_RETRACE_LIMIT"):
        assert name in env_vars.ENV_VARS, name
        assert env_vars.ENV_VARS[name][0] == "honored", name


def test_the_scan_dispatch_and_executable_cache_names_are_gone():
    """PR 9's four names left with their mechanisms (PR 29): none is
    registered, and no use-site in the tree reads one."""
    # spelled in pieces: a grep of the tree for the names stays empty
    scan, cache = "MX_" + "SUPER" + "STEP", "MX_" + "EXECUTABLE" + "_CACHE"
    gone = {scan, scan + "_FORCE_CPU", cache, cache + "_DIR"}
    assert not gone & set(env_vars.ENV_VARS)
    assert not gone & set(_scan(registry=set()))
