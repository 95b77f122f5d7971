"""The documents name what exists.

Every path of this repository that ``README.md``, ``docs/*.md`` and
``PERF.md`` section 3 name in backticks is in the checkout, and every
``--flag`` they attribute to ``tfrun``, ``tfserve`` (and its subcommands)
or the replica is an option of that parser, read from the parser's
actions.  One case a (document, path) or (cli, flag) pair, so a stale
name fails under its own id; no document is edited to make a case pass
except by making it true.  jax-free."""

import fnmatch
import glob
import os
import re
import subprocess

import pytest

from tfmesos_tpu import cli
from tfmesos_tpu.fleet import replica

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUFFIXES = (".py", ".md", ".json", ".jsonl", ".sh")

#: Named in the documents and rightly absent from a fresh checkout:
#: git-ignored build outputs, and the reference's own paths that
#: ``docs/MIGRATION.md`` and ``README.md`` map from.
ALLOWED = ("native/*.so", ".jax_cache/", "chiprun_out/*", "benchmark_out/*",
           "tfmesos/*", "script/tfrun")


def _read(doc: str) -> str:
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    if doc == "PERF.md":
        text = text[text.index("## 3. Layers"):text.index("## 4. Cells")]
    return text


def _code(text: str, blocks: bool = False):
    """Inline code spans outside fenced blocks and, with ``blocks``, the
    lines of the fenced blocks with their backslash continuations
    joined."""
    fenced, pending = False, ""
    for line in text.splitlines():
        if line.lstrip().startswith("```"):
            fenced = not fenced
        elif not fenced:
            yield from re.findall(r"`([^`\n]+)`", line)
        elif blocks:
            pending = f"{pending} {line.rstrip().rstrip(chr(92))}".strip()
            if not line.rstrip().endswith("\\"):
                yield pending
                pending = ""


def _checkout():
    """Every file and directory of the checkout, as paths from its root:
    what git tracks or would, else (no git: the driver's copy holds only
    committed files) what is on disk."""
    try:
        files = subprocess.run(
            ["git", "ls-files", "--cached", "--others",
             "--exclude-standard"], cwd=REPO, text=True,
            capture_output=True, check=True).stdout.split("\n")
        files = [f for f in files
                 if f and os.path.exists(os.path.join(REPO, f))]
    except (OSError, subprocess.CalledProcessError):
        files = []
    if not files:
        files = [os.path.relpath(os.path.join(d, f), REPO)
                 for d, _, names in os.walk(REPO) for f in names
                 if ".git" not in d.split(os.sep)]
    paths = set(files)
    for f in files:
        parts = f.split("/")
        paths.update("/".join(parts[:i]) for i in range(1, len(parts)))
    return paths


_PATHS = _checkout()
_DIR_NAMES = {p.rsplit("/", 1)[-1] for p in _PATHS
              if os.path.isdir(os.path.join(REPO, p))}


def _is_path(token: str) -> bool:
    """A token that names a path: a known suffix, or slash-separated path
    characters under the name of one of the checkout's directories (so
    ``max_len/pos`` and ``text/event-stream`` are not paths)."""
    if token.startswith(("/", "~", "http", "<", "-")) \
            or not re.fullmatch(r"[\w.\-*/]+", token):
        return False
    if token.endswith(SUFFIXES):
        return True
    return "/" in token and (
        token.split("/", 1)[0] in _DIR_NAMES
        or any(fnmatch.fnmatch(token, a) for a in ALLOWED))


def _named_paths():
    docs = ["README.md", "PERF.md"] + sorted(
        os.path.relpath(p, REPO)
        for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
    cases = set()
    for doc in docs:
        for span in _code(_read(doc)):
            if re.search(r"\s", span):
                continue
            # ``path:line``, ``path:function`` and ``path::test`` name the
            # path; ``<model>`` in one stands for any name
            token = re.sub(r"<[^>]*>", "*", span.split(":", 1)[0])
            if _is_path(token):
                cases.add((doc, token))
    return sorted(cases)


def _exists(token: str) -> bool:
    """``token`` is a path of the checkout from its root or from any
    directory of it (``serving.py``, ``ops/attention.py``), a glob with a
    match, or ``dir/module.name`` where ``dir/module.py`` says ``name``."""
    token = token.rstrip("/")

    def find(pattern):
        return [p for p in _PATHS if fnmatch.fnmatchcase(p, pattern)
                or fnmatch.fnmatchcase(p, "*/" + pattern)]

    if find(token):
        return True
    head, _, last = token.rpartition("/")
    if not token.endswith(SUFFIXES) and "." in last:
        module, _, name = last.partition(".")
        for path in find(f"{head}/{module}.py"):
            with open(os.path.join(REPO, path)) as f:
                if re.search(rf"\b{re.escape(name.split('.')[0])}\b",
                             f.read()):
                    return True
    return False


@pytest.mark.parametrize(
    "doc,path", [pytest.param(d, p, id=f"{d}:{p}") for d, p in _named_paths()])
def test_named_path_exists(doc, path):
    if any(fnmatch.fnmatch(path, a) for a in ALLOWED):
        return
    assert _exists(path), f"{doc} names `{path}`, which is not in the checkout"


# -- flags ------------------------------------------------------------------

_SUBCOMMANDS = {
    "submit": cli.build_submit_parser, "batch": cli.build_batch_parser,
    "trace": cli.build_trace_parser, "simulate": cli.build_simulate_parser,
    "gateways": cli.build_gateways_parser,
    "metrics": cli.build_metrics_parser,
    "swap-adapter": cli.build_swap_adapter_parser,
    "rollout": cli.build_rollout_parser,
}
_WRAPPERS = ("timeout", "env", "nohup", "python", "python3")


def _options(parser) -> set:
    return {o for a in parser._actions for o in a.option_strings}


def _parsers():
    out = {"tfrun": _options(cli.build_parser()),
           "tfserve": _options(cli.build_serve_parser()),
           "replica": _options(replica.build_parser())}
    for name, build in _SUBCOMMANDS.items():
        out[f"tfserve {name}"] = _options(build())
    # README's ``cli.py`` row lists flags of either command bare
    out["cli.py"] = set().union(*out.values())
    return out


def _attribute(command: str):
    """``(cli, words)`` when ``command`` starts, behind environment
    assignments and wrappers, with one of the CLIs' names; else None."""
    words = command.split("#", 1)[0].split()
    while words and (re.match(r"[A-Z_][A-Z0-9_]*=", words[0])
                     or words[0] in _WRAPPERS or words[0].isdigit()):
        words.pop(0)
    if words[:2] == ["-m", "tfmesos_tpu.fleet.replica"]:
        return "replica", words[2:]
    if not words:
        return None
    first, rest = words[0], words[1:]
    if first in ("tfrun", "bin/tfrun"):
        # behind ``--`` is the user's command, not tfrun's
        return "tfrun", rest[:rest.index("--")] if "--" in rest else rest
    if first in ("tfserve", "bin/tfserve"):
        if rest and rest[0] in _SUBCOMMANDS:
            return f"tfserve {rest[0]}", rest[1:]
        return "tfserve", rest
    if first in ("fleet.replica", "tfmesos_tpu.fleet.replica"):
        return "replica", rest
    return None


def _flags(words):
    for w in words:
        m = re.fullmatch(r"(--[a-z][a-z0-9-]*|-[A-Za-z]{1,2})(=.*)?", w)
        if m:
            yield m.group(1)


def _named_flags():
    cases = set()
    for doc in ("README.md", "docs/SERVING.md", "docs/MIGRATION.md"):
        for command in _code(_read(doc), blocks=True):
            got = _attribute(command)
            if got:
                cases.update((got[0], f) for f in _flags(got[1]))
    row = next(line for line in _read("README.md").splitlines()
               if line.startswith("| `tfmesos_tpu/cli.py`"))
    for span in re.findall(r"`([^`\n]+)`", row):
        if not _attribute(span):
            cases.update(("cli.py", f) for f in _flags(span.split())
                         if f.startswith("--"))
    return sorted(cases)


_PARSERS = _parsers()


@pytest.mark.parametrize(
    "name,flag", [pytest.param(n, f, id=f"{n.replace(' ', '_')}:{f}")
                  for n, f in _named_flags()])
def test_named_flag_parses(name, flag):
    assert flag in _PARSERS[name], \
        f"the documents give {name} a {flag} its parser does not have"
