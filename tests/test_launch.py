"""Launcher plumbing: compile-cache placement and CLI flags."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.train import build_parser

REPO = Path(__file__).resolve().parents[1]


def _restoring_cache_dir(fn):
    was = jax.config.jax_compilation_cache_dir
    try:
        return fn()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself: the
    helper reports it and sets no other directory in code."""
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    got = _restoring_cache_dir(compile_cache.enable_compile_cache)
    assert got == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_ignored_repo_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)

    def enable():
        got = compile_cache.enable_compile_cache()
        return got, jax.config.jax_compilation_cache_dir

    got, configured = _restoring_cache_dir(enable)
    assert got == configured == str(REPO / ".jax_cache")
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_resume_flag_can_be_turned_off():
    ap = build_parser()
    assert ap.parse_args([]).resume is True
    assert ap.parse_args(["--no-resume"]).resume is False
    assert ap.parse_args(["--ckpt", ""]).ckpt == ""


@pytest.mark.parametrize("argv, want", [
    ([], (None, (5, 4))),
    (["--profile-dir", "/p", "--profile-steps", "0:2"], ("/p", (0, 2))),
])
def test_profile_flags(argv, want):
    args = build_parser().parse_args(argv)
    assert (args.profile_dir, args.profile_steps) == want


@pytest.mark.parametrize("bad", ["5", "5:0", "-1:4", "a:b", "5:4:3"])
def test_profile_steps_rejects_malformed_ranges(bad, capsys):
    with pytest.raises(SystemExit):
        build_parser().parse_args(["--profile-steps", bad])
    assert "FIRST:COUNT" in capsys.readouterr().err
