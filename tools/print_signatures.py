#!/usr/bin/env python
"""API-freeze check: print every public API signature, hashed.

Analog of the reference's tools/print_signatures.py (the CI approval
check that flags any public-API signature change). Usage:

    python tools/print_signatures.py paddle_tpu > api.spec
    # ... after changes ...
    python tools/print_signatures.py paddle_tpu | diff api.spec -
"""

from __future__ import annotations

import hashlib
import importlib
import inspect
import os
import pkgutil
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _signature(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"
    # what differs from one process to the next is no API: the address
    # in a default's repr, the order a set of strings prints in
    sig = re.sub(r" at 0x[0-9a-f]+", "", sig)
    return re.sub(
        r"frozenset\(\{([^{}]*)\}\)",
        lambda m: "frozenset({%s})" % ", ".join(sorted(m[1].split(", "))),
        sig)


def iter_api(root_name: str):
    root = importlib.import_module(root_name)
    seen_modules = {root_name}
    modules = [root]
    if hasattr(root, "__path__"):
        for info in pkgutil.walk_packages(root.__path__,
                                          prefix=root_name + "."):
            if info.name in seen_modules:
                continue
            # built native artifacts (_<name>-<srchash>-<flaghash>.so)
            # carry content hashes in their filenames — they are build
            # outputs, not API surface, and would churn the snapshot on
            # every C++ edit
            if info.name.rsplit(".", 1)[-1].startswith("_"):
                continue
            seen_modules.add(info.name)
            try:
                modules.append(importlib.import_module(info.name))
            except Exception as e:  # report broken modules, don't crash
                yield info.name, f"<import error: {type(e).__name__}>"
    for mod in modules:
        public = getattr(mod, "__all__", None)
        if public is None:
            public = [n for n in vars(mod) if not n.startswith("_")]
        for name in sorted(public):
            obj = getattr(mod, name, None)
            if obj is None or inspect.ismodule(obj):
                continue
            qual = f"{mod.__name__}.{name}"
            if inspect.isclass(obj):
                yield qual, f"class{_signature(obj)}"
                for mname, m in sorted(vars(obj).items()):
                    if mname.startswith("_") and mname != "__init__":
                        continue
                    if inspect.isfunction(m):
                        yield f"{qual}.{mname}", _signature(m)
            elif callable(obj):
                yield qual, _signature(obj)


def main(argv=None):
    argv = argv if argv is not None else sys.argv[1:]
    root = argv[0] if argv else "paddle_tpu"
    for qual, sig in sorted(iter_api(root)):
        digest = hashlib.md5(sig.encode()).hexdigest()[:10]
        print(f"{qual} {digest} {sig}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
