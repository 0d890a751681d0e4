"""Tests for the can_access pre-check (reference tools/access.py parity)."""

import os

from aggregathor_tpu.utils import can_access


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


def test_hw_peaks_keyed_by_device_kind():
    """utils/hw: one sourced table keyed by device_kind; a kind that is not
    in it raises and names itself — never another chip's peak."""
    import types

    import pytest

    from aggregathor_tpu.utils import hw

    v5e = hw.peaks(types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu"))
    assert v5e.bf16_flops == 1.97e14 and v5e.hbm_bytes_per_s == 8.19e11
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="cpu-kind-nobody-listed"):
        hw.peaks(types.SimpleNamespace(device_kind="cpu-kind-nobody-listed",
                                       platform="cpu"))


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
