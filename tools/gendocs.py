"""Render HTML API docs for ``distributedfft_tpu`` into ``documentation/``.

The reference ships Doxygen output (``/root/reference/Doxyfile`` →
``documentation/html``); this is the TPU repo's equivalent, built on the
STDLIB ``pydoc`` renderer because the environment bakes in neither pdoc
nor sphinx (and installs are disallowed). The docstrings are the
documentation source — they carry the design rationale, measured numbers
and reference file:line provenance — so a plain renderer loses nothing
that matters.

Usage (from the repo root):
    python tools/gendocs.py          # writes documentation/*.html
    make docs                        # same, via the root Makefile
"""

from __future__ import annotations

import importlib
import os
import pkgutil
import pydoc
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
OUT = os.path.join(REPO, "documentation")
PACKAGE = "distributedfft_tpu"


def iter_module_names() -> list:
    """All importable module names under the package, package first."""
    sys.path.insert(0, REPO)
    pkg = importlib.import_module(PACKAGE)
    names = [PACKAGE]
    for info in pkgutil.walk_packages(pkg.__path__, prefix=PACKAGE + "."):
        names.append(info.name)
    return names


def main() -> int:
    # Stay on the CPU: importing the package imports jax, and on a chip
    # host the docs build must not claim the chip.
    import jax
    jax.config.update("jax_platforms", "cpu")

    os.makedirs(OUT, exist_ok=True)
    os.chdir(OUT)  # pydoc.writedoc writes into the current directory
    written, failed = [], []
    for name in iter_module_names():
        try:
            importlib.import_module(name)
            pydoc.writedoc(name)
            written.append(name)
        except Exception as e:  # noqa: BLE001 — skip, report, keep going
            failed.append((name, f"{type(e).__name__}: {e}"))

    index = ["<html><head><title>distributedfft_tpu API</title></head>",
             "<body><h1>distributedfft_tpu API reference</h1>",
             "<p>Rendered from the package docstrings by tools/gendocs.py "
             "(stdlib pydoc). Docstrings carry design rationale, measured "
             "numbers and reference-code provenance (file:line into the "
             "upstream CUDA/MPI implementation).</p><ul>"]
    for name in written:
        index.append(f'<li><a href="{name}.html">{name}</a></li>')
    index.append("</ul></body></html>")
    with open("index.html", "w") as f:
        f.write("\n".join(index))

    print(f"wrote {len(written)} module pages + index.html to {OUT}")
    for name, err in failed:
        print(f"SKIPPED {name}: {err}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
