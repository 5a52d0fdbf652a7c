"""Version advisory tests (ref tests/test_jax_compat.py: version-tuple
parsing and warning behavior via monkeypatch)."""

import warnings

import pytest

from mpi4jax_tpu.utils.jax_compat import (
    SUPPORTED_JAX_VERSION,
    check_jax_version,
    versiontuple,
)


@pytest.mark.parametrize(
    "raw, expected",
    [
        ("0.9.0", (0, 9, 0)),
        ("0.4.24", (0, 4, 24)),
        ("0.10.0.dev20260101", (0, 10, 0)),
        ("1.0.0rc1", (1, 0, 0)),
    ],
)
def test_versiontuple(raw, expected):
    assert versiontuple(raw) == expected


def test_supported_version_passes_silently():
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        check_jax_version(SUPPORTED_JAX_VERSION)


def test_newer_jax_warns():
    with pytest.warns(UserWarning, match="latest supported JAX version"):
        check_jax_version("99.0.0")


def test_newer_jax_warning_silenced(monkeypatch):
    monkeypatch.setenv("MPI4JAX_TPU_NO_WARN_JAX_VERSION", "1")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        check_jax_version("99.0.0")


def test_too_old_jax_raises():
    with pytest.raises(RuntimeError, match="requires jax>="):
        check_jax_version("0.4.24")

    with pytest.raises(RuntimeError, match="requires jax>="):
        check_jax_version("0.8.2")
