"""Tests for the can_access pre-check (reference tools/access.py parity)."""

import glob
import os
import re

import pytest

from aggregathor_tpu.utils import can_access

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_can_access_file(tmp_path):
    f = tmp_path / "x.txt"
    f.write_text("hi")
    assert can_access(str(f), read=True)
    assert can_access(str(f), read=True, write=True)
    assert not can_access(str(tmp_path / "missing"), read=True)


def test_can_access_dir_recurse(tmp_path):
    sub = tmp_path / "sub"
    sub.mkdir()
    (sub / "a.txt").write_text("a")
    assert can_access(str(tmp_path), read=True, recurse=True)
    if os.geteuid() != 0:  # root bypasses mode bits
        os.chmod(sub / "a.txt", 0o000)
        assert not can_access(str(tmp_path), read=True, recurse=True)
        assert can_access(str(tmp_path), read=True, recurse=False)
        os.chmod(sub / "a.txt", 0o644)


def test_can_access_write_only_check(tmp_path):
    f = tmp_path / "w.txt"
    f.write_text("")
    assert can_access(str(f), write=True)


def test_state_json_roundtrip_and_corruption(tmp_path):
    """utils/state: atomic save + tolerant load (a resumable benchmark can
    be killed mid-write; a half-written or non-dict file must read as the
    default, never raise)."""
    from aggregathor_tpu.utils.state import load_json, save_json_atomic

    path = str(tmp_path / "s.json")
    assert load_json(path) == {}
    assert load_json(path, default={"done": []}) == {"done": []}
    save_json_atomic(path, {"a": 1})
    assert load_json(path) == {"a": 1}
    with open(path, "w") as fd:
        fd.write('{"a": 1')  # truncated by a kill mid-write
    assert load_json(path) == {}
    with open(path, "w") as fd:
        fd.write('[1, 2]')  # valid JSON, wrong top-level type
    assert load_json(path, default={"done": []}) == {"done": []}


def test_hw_names_no_peaks():
    """utils/hw answers one question (``on_tpu``); the chip's published peaks
    live with the benchmark alone (grid/peaks.json), not in a second table."""
    from aggregathor_tpu.utils import hw

    assert callable(hw.on_tpu) and hw.on_tpu() is False  # the suite runs on the CPU
    assert not [name for name in vars(hw) if "peak" in name.lower()]
    assert os.path.exists(os.path.join(_REPO, "grid", "peaks.json"))


#: README, the index of records, every page of docs/ and the builders' skill;
#: not PERF.md or ROADMAP.md, which name files that later PRs are to bring.
_DOCUMENTS = ["README.md", "BENCHMARKS.md", ".claude/skills/verify/SKILL.md"] + sorted(
    "docs/" + name for name in os.listdir(os.path.join(_REPO, "docs")) if name.endswith(".md"))
#: A relative path to a script or a record.  Not taken: absolute paths, and
#: what hangs off a placeholder or a variable (``<snapshot>.manifest.json``,
#: ``$out/x.json``) — a file that a documented command WRITES is named under
#: ``/tmp`` for that reason.
_PATH = re.compile(r"(?<![\w/.$~<>{*-])([\w.*-]+(?:/[\w.*-]+)*\.(?:py|sh|json))(?![\w/*])")


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_documents_name_files_that_exist(document):
    """Every path to a ``.py``, ``.sh`` or ``.json`` that a document back-quotes
    or puts on a command line is in the tree, from the repository's root or
    from the package's directory (a ``*`` globs).  A reader who follows a
    document must not land on an instrument that is gone."""
    with open(os.path.join(_REPO, document)) as fd:
        text = fd.read()
    fenced = re.findall(r"```.*?```", text, re.S)
    quoted = re.findall(r"`([^`\n]+)`", re.sub(r"```.*?```", "", text, flags=re.S))
    named = {m.group(1) for span in fenced + quoted for m in _PATH.finditer(span)}
    dangling = sorted(
        path for path in named
        if not any(glob.glob(os.path.join(base, path))
                   for base in (_REPO, os.path.join(_REPO, "aggregathor_tpu"))))
    assert not dangling, "%s names files that are not in the tree: %s" % (document, dangling)


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """utils/compile_cache: with JAX_COMPILATION_CACHE_DIR in the environment
    the directory setting is left alone (JAX reads it); without it the cache
    goes to the fixed <checkout>/.jax_cache; on a CPU backend nothing is
    placed at all."""
    import jax

    from aggregathor_tpu.utils import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update", lambda name, value: updates.append((name, value)))

    def placed_dirs():
        return [value for name, value in updates if name.endswith("cache_dir")]

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.place_compile_cache() is None  # the suite runs on CPU
    assert updates == []

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.place_compile_cache() == os.path.join(repo, ".jax_cache")
    assert placed_dirs() == [os.path.join(repo, ".jax_cache")]

    del updates[:]
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.place_compile_cache() == "/some/dir"
    assert placed_dirs() == []
