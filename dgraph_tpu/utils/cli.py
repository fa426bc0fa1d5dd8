"""Tiny dataclass-CLI bridge: one config tree + ``--key value`` overrides.

Replaces the reference's three config mechanisms (fire.Fire CLIs, dataclass
trees, scattered env flags — SURVEY.md §5 config) with one: a dataclass is
the schema, the CLI overrides fields by name (dotted for nesting).
"""

from __future__ import annotations

import argparse
import dataclasses
import typing


def parse_config(config_cls, argv=None):
    """Build ``config_cls()`` then apply ``--field value`` / ``--a.b value``
    overrides, coercing to the annotated field type."""
    import sys

    from dgraph_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--help" in argv or "-h" in argv:
        print(config_cls.__doc__ or config_cls.__name__)
        for f in dataclasses.fields(config_cls):
            print(f"  --{f.name} (default {f.default!r})")
        raise SystemExit(0)
    cfg = config_cls()

    pairs = []
    it = iter(argv)
    for tok in it:
        if tok.startswith("--"):
            key = tok[2:]
            if "=" in key:
                pairs.append(key.split("=", 1))
            else:
                pairs.append((key, next(it, "true")))
        elif "=" in tok:
            pairs.append(tok.split("=", 1))
        else:
            raise SystemExit(f"override must be key=value or --key value, got {tok!r}")

    for key, raw in pairs:
        obj, parts = cfg, key.split(".")
        for p in parts[:-1]:
            obj = getattr(obj, p)
        leaf = parts[-1]
        # get_type_hints resolves STRING annotations (`from __future__
        # import annotations` stringifies every ann — 'Optional[int]',
        # 'int | None', ... would all coerce to str via f.type); it is also
        # the membership check (hasattr would admit properties/methods and
        # then KeyError below)
        hints = typing.get_type_hints(type(obj))
        if leaf not in hints:
            raise SystemExit(f"unknown config field: {key}")
        setattr(obj, leaf, _coerce(raw, hints[leaf]))
    return cfg


def _coerce(raw: str, ann):
    import types

    origin = typing.get_origin(ann)
    if origin in (typing.Union, types.UnionType):  # Optional[X] / X | None
        args = [a for a in typing.get_args(ann) if a is not type(None)]
        if raw.lower() in ("none", "null"):
            return None
        ann = args[0]
    if ann is bool:
        return raw.strip().lower() in ("1", "true", "yes", "on")
    if ann in (int, float, str):
        return ann(raw)
    return raw
