"""The tree's account of itself is true: every flag is read, the documents
name files that exist, and the runtime's lowest layer knows no benchmark.
Cheap, no cluster: the files are parsed, never imported."""

import ast
import os
import re

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(_ROOT, "ray_tpu")
_CONFIG = os.path.join(_PKG, "_private", "config.py")


def _parse(path):
    with open(path, encoding="utf-8") as f:
        return ast.parse(f.read(), filename=path)


def _py_files(top):
    for dirpath, dirnames, filenames in os.walk(top):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for name in filenames:
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


def test_every_config_flag_is_read_outside_config():
    """A flag counts as read where code outside config.py says
    ``RayConfig.<name>``, passes ``"<name>"`` as a string (get/set by name)
    or reads its ``RAY_TPU_<NAME>`` environment key.  Stricter than the
    lint's dead-flag rule, which any attribute of that name satisfies."""
    flags = [n.args[0].value for n in ast.walk(_parse(_CONFIG))
             if isinstance(n, ast.Call)
             and getattr(n.func, "id", None) in ("_d", "define")
             and n.args and isinstance(n.args[0], ast.Constant)]
    assert len(flags) == len(set(flags)) > 0

    reads = set()
    for path in _py_files(_PKG):
        if path == _CONFIG:
            continue
        for n in ast.walk(_parse(path)):
            if isinstance(n, ast.Attribute) \
                    and getattr(n.value, "id", None) == "RayConfig":
                reads.add(n.attr)
            elif isinstance(n, ast.Constant) and isinstance(n.value, str):
                reads.add(n.value)
    unread = [f for f in flags
              if f not in reads and "RAY_TPU_" + f.upper() not in reads]
    assert unread == [], f"flags nothing reads: {unread}"


def _tree_paths():
    """Every file of the checkout, '/'-separated and root-relative; hidden
    directories and the chip tool's output are not part of the tree."""
    out = []
    for dirpath, dirnames, filenames in os.walk(_ROOT):
        dirnames[:] = [d for d in dirnames
                       if not d.startswith(".")
                       and d not in ("__pycache__", "chiprun_out")]
        rel = os.path.relpath(dirpath, _ROOT)
        for name in filenames:
            out.append(name if rel == "." else
                       rel.replace(os.sep, "/") + "/" + name)
    return out


def _expand_braces(token):
    m = re.search(r"\{([^{}]*)\}", token)
    if m is None:
        return [token]
    return [x for alt in m.group(1).split(",")
            for x in _expand_braces(token[:m.start()] + alt + token[m.end():])]


@pytest.mark.parametrize("doc", ["README.md", "docs/ARCHITECTURE.md"])
def test_documents_name_files_that_exist(doc):
    """Every back-ticked path ending in .py, .json or .md names a file of
    the tree: in full from the root, or by its trailing components
    (`_private/config.py`, `nodelet.py`), the documents' shorthand.
    `{a,b}` expands, `*` matches within one component; the reference's own
    tree (`python/ray/...`) is not ours to check."""
    with open(os.path.join(_ROOT, doc), encoding="utf-8") as f:
        text = f.read()
    tokens = set(re.findall(
        r"`([^`\s]+?\.(?:py|json|md))(?:[:#][^`]*)?`", text))
    assert tokens, f"{doc} names no file at all?"
    paths = _tree_paths()
    missing = []
    for token in sorted(tokens):
        if token.startswith("python/ray/"):
            continue
        for want in _expand_braces(token):
            pat = re.compile(
                "(?:^|/)" + re.escape(want.lstrip("./")).replace(
                    r"\*", "[^/]*") + "$")
            if not any(pat.search(p) for p in paths):
                missing.append(want)
    assert missing == [], f"{doc} names files that do not exist: {missing}"


def test_runtime_core_imports_no_benchmark():
    """The worker's entry point, the core worker, the nodelet and the GCS
    import nothing whose module path says `bench` (so neither `perfbench`):
    a measurement may know the runtime, never the reverse.  The whole AST
    is walked, because the hook this rule was written after sat inside
    ``worker_main.main()``."""
    private = os.path.join(_PKG, "_private")
    files = [os.path.join(private, n)
             for n in ("worker_main.py", "core_worker.py", "nodelet.py")]
    files += list(_py_files(os.path.join(private, "gcs")))
    assert len(files) > 3
    offenders = []
    for path in files:
        for n in ast.walk(_parse(path)):
            if isinstance(n, ast.Import):
                names = [a.name for a in n.names]
            elif isinstance(n, ast.ImportFrom):
                names = [(n.module or "") + "." + a.name for a in n.names]
            else:
                continue
            offenders += [(os.path.relpath(path, _ROOT), n.lineno, name)
                          for name in names if "bench" in name.lower()]
    assert offenders == []
