"""launch/env.py: the process-environment perf preset.

Pure host logic — no jax, no subprocess exec. The tcmalloc probe is
driven by monkeypatching ``os.path.exists`` so the tests pin BOTH
branches (present/absent) regardless of what the host has installed.
"""
import os

import pytest

from repro.launch import env as E


def _with_tcmalloc(monkeypatch, path):
    """Make exactly ``path`` (a TCMALLOC_CANDIDATES entry or None) exist."""
    monkeypatch.setattr(os.path, "exists", lambda p: p == path)


# ------------------------------------------------------------- find_tcmalloc

def test_find_tcmalloc_picks_first_existing(monkeypatch):
    want = E.TCMALLOC_CANDIDATES[1]
    _with_tcmalloc(monkeypatch, want)
    assert E.find_tcmalloc() == want


def test_find_tcmalloc_none_when_absent(monkeypatch):
    _with_tcmalloc(monkeypatch, None)
    assert E.find_tcmalloc() is None


# ----------------------------------------------------------- XLA flag merge

def test_merge_adds_perf_flags_to_empty():
    merged = E._merge_xla_flags("")
    for f in E.XLA_PERF_FLAGS:
        assert f in merged.split()


def test_merge_caller_wins_on_same_flag():
    """A caller-set value of the same flag must NOT be clobbered or
    duplicated — only flags the caller didn't set are added."""
    merged = E._merge_xla_flags("--xla_step_marker_location=0")
    flags = merged.split()
    assert flags.count("--xla_step_marker_location=0") == 1
    assert "--xla_step_marker_location=1" not in flags


def test_merge_preserves_unrelated_flags():
    merged = E._merge_xla_flags("--xla_force_host_platform_device_count=8")
    assert "--xla_force_host_platform_device_count=8" in merged.split()
    assert "--xla_step_marker_location=1" in merged.split()


# ----------------------------------------------------------------- perf_env

def test_perf_env_sets_preload_when_tcmalloc_found(monkeypatch):
    tc = E.TCMALLOC_CANDIDATES[0]
    _with_tcmalloc(monkeypatch, tc)
    delta = E.perf_env({})
    assert delta["LD_PRELOAD"] == tc
    assert delta["TF_CPP_MIN_LOG_LEVEL"] == "4"
    assert "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD" in delta


def test_perf_env_prepends_not_duplicates_preload(monkeypatch):
    tc = E.TCMALLOC_CANDIDATES[0]
    _with_tcmalloc(monkeypatch, tc)
    # existing preload of something else -> prepended
    delta = E.perf_env({"LD_PRELOAD": "/lib/other.so"})
    assert delta["LD_PRELOAD"] == tc + os.pathsep + "/lib/other.so"
    # already preloaded -> untouched
    delta = E.perf_env({"LD_PRELOAD": tc})
    assert "LD_PRELOAD" not in delta


def test_perf_env_fallback_without_tcmalloc(monkeypatch):
    """No tcmalloc on the host: the preset must still work — no
    LD_PRELOAD of a missing path (which would break every child exec)."""
    _with_tcmalloc(monkeypatch, None)
    delta = E.perf_env({})
    assert "LD_PRELOAD" not in delta
    assert "--xla_step_marker_location=1" in delta["XLA_FLAGS"]


def test_perf_env_respects_caller_values(monkeypatch):
    _with_tcmalloc(monkeypatch, None)
    base = {"TF_CPP_MIN_LOG_LEVEL": "0",
            "XLA_FLAGS": "--xla_step_marker_location=0"}
    delta = E.perf_env(base)
    assert "TF_CPP_MIN_LOG_LEVEL" not in delta
    assert "XLA_FLAGS" not in delta     # nothing to add -> no churn


def test_apply_mutates_and_returns_delta(monkeypatch):
    _with_tcmalloc(monkeypatch, None)
    environ = {}
    delta = E.apply(environ)
    assert environ == delta
    assert "--xla_step_marker_location=1" in environ["XLA_FLAGS"]


# --------------------------------------------------------------------- CLI

def test_main_sh_emits_evalable_exports(monkeypatch, capsys):
    _with_tcmalloc(monkeypatch, E.TCMALLOC_CANDIDATES[0])
    monkeypatch.setattr(os, "environ", {})
    E.main(["--sh"])
    out = capsys.readouterr().out
    lines = [l for l in out.splitlines() if l]
    assert lines == sorted(lines)
    for line in lines:
        assert line.startswith("export ")
        k, v = line[len("export "):].split("=", 1)
        assert v.startswith("'") and v.endswith("'")
    assert any(l.startswith("export LD_PRELOAD=") for l in lines)


def test_main_plain_prints_kv(monkeypatch, capsys):
    _with_tcmalloc(monkeypatch, None)
    monkeypatch.setattr(os, "environ", {})
    E.main([])
    out = capsys.readouterr().out
    assert "XLA_FLAGS=" in out
    assert "export" not in out


def test_main_exec_applies_preset(monkeypatch):
    """`-- cmd` re-execs with the preset merged into the environment."""
    _with_tcmalloc(monkeypatch, None)
    seen = {}

    def fake_exec(prog, argv, env):
        seen.update(prog=prog, argv=argv, env=env)

    monkeypatch.setattr(os, "execvpe", fake_exec)
    monkeypatch.setattr(os, "environ", {"HOME": "/root"})
    E.main(["--", "echo", "hi"])
    assert seen["prog"] == "echo" and seen["argv"] == ["echo", "hi"]
    assert seen["env"]["HOME"] == "/root"
    assert "--xla_step_marker_location=1" in seen["env"]["XLA_FLAGS"]


def test_sh_quote_single_quotes():
    assert E._sh_quote("a'b") == "'a'\\''b'"


# ------------------------------------------------------ enable_compile_cache

@pytest.fixture
def jax_cache_config():
    """Restore JAX's compile-cache directory after the test."""
    import jax
    was = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_var_is_left_to_jax(monkeypatch, jax_cache_config):
    before = jax_cache_config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert E.enable_compile_cache() == "/elsewhere/cache"
    assert jax_cache_config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_ignored_checkout_path(
        monkeypatch, jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = E.enable_compile_cache()
    assert path == os.path.join(repo, ".jax_cache")
    assert jax_cache_config.jax_compilation_cache_dir == path
    assert E.enable_compile_cache() == path
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
