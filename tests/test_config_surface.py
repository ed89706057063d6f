"""The option surface, held to the tree: every `Config` field is read by
the package, every field owns its environment name, what `set_config`
exports is the name the field reads back, and a removed option is
refused by name.
"""

import ast
import dataclasses
import os

import pytest

import bodo_tpu
from bodo_tpu.config import Config, config, set_config

_PKG = os.path.dirname(os.path.abspath(bodo_tpu.__file__))


def _env_name(f: dataclasses.Field) -> str:
    """The `BODO_TPU_*` name a field's default reads (a constant of its
    `default_factory` lambda)."""
    names = [c for c in f.default_factory.__code__.co_consts
             if isinstance(c, str) and c.startswith("BODO_TPU_")]
    assert len(names) == 1, (f.name, names)
    return names[0]


def _reads_in(path: str) -> set:
    """Names a module reads off the config object: `config.<name>`
    under whatever name the module imported it, the string in
    `getattr(config, "<name>", ...)` / `resilience._cfg("<name>", ...)`,
    and a field's environment name where code takes the exported value
    from `os.environ` itself (docstrings that mention one do not count)."""
    with open(path) as fh:
        tree = ast.parse(fh.read(), path)
    aliases = {a.asname or a.name
               for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)
               and n.module == "bodo_tpu.config"
               for a in n.names if a.name == "config"}
    out = set()
    for n in ast.walk(tree):
        if isinstance(n, ast.Attribute) and isinstance(n.value, ast.Name) \
                and n.value.id in aliases:
            out.add(n.attr)
        elif isinstance(n, ast.Constant) and isinstance(n.value, str) \
                and n.value.startswith("BODO_TPU_"):
            out.add(n.value)
        elif isinstance(n, ast.Call):
            fn = n.func
            fname = fn.id if isinstance(fn, ast.Name) else \
                fn.attr if isinstance(fn, ast.Attribute) else ""
            if fname == "getattr" and len(n.args) >= 2 \
                    and isinstance(n.args[0], ast.Name) \
                    and n.args[0].id in aliases:
                arg = n.args[1]
            elif fname == "_cfg" and n.args:
                arg = n.args[0]
            else:
                continue
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                out.add(arg.value)
    return out


def test_every_field_has_a_reader():
    read = set()
    for root, _dirs, files in os.walk(_PKG):
        for fn in files:
            p = os.path.join(root, fn)
            if fn.endswith(".py") and p != os.path.join(_PKG, "config.py"):
                read |= _reads_in(p)
    unread = [f.name for f in dataclasses.fields(Config)
              if f.name not in read and _env_name(f) not in read]
    assert unread == [], f"Config fields nothing in bodo_tpu/ reads: {unread}"


def _other_value(f: dataclasses.Field, cur, tmp_path):
    # by the declared type: a test before this one may have left 1 for True
    if f.type == "bool":
        return not cur
    if f.type in ("int", "float"):
        return cur + 1
    if f.name == "faults":
        return "spawn.worker_start=raise:OSError"
    return str(tmp_path / f.name)


def test_env_names_are_owned_and_exports_read_back(monkeypatch, tmp_path):
    fs = dataclasses.fields(Config)
    names = [_env_name(f) for f in fs]
    dup = sorted({n for n in names if names.count(n) > 1})
    assert dup == [], f"environment names shared by two fields: {dup}"

    # a scratch environment without the fields' names: what set_config
    # exports lands here and is gone with the test
    monkeypatch.setattr(os, "environ", {k: v for k, v in os.environ.items()
                                        if k not in names})
    exported = set()
    for f, env in zip(fs, names):
        cur = getattr(config, f.name)
        before = dict(os.environ)
        try:
            set_config(**{f.name: _other_value(f, cur, tmp_path)})
            changed = {k for k in set(before) | set(os.environ)
                       if before.get(k) != os.environ.get(k)}
            assert changed <= {env}, (f.name, env, changed)
            if changed:
                exported.add(f.name)
                # a worker that copies this environment builds the same
                # value from the name the field itself reads
                assert getattr(Config(), f.name) == getattr(config, f.name)
        finally:
            set_config(**{f.name: cur})
    for fam in ("faults", "gang_id", "elastic", "elastic_dir", "lockstep",
                "lockstep_dir", "progcheck", "progcheck_enforce",
                "trace_dir", "telemetry"):
        assert fam in exported, f"set_config no longer exports {fam}"


def test_removed_option_is_refused_by_name():
    with pytest.raises(ValueError, match="dump_plans"):
        set_config(dump_plans=True)
    assert not hasattr(config, "dump_plans")
