"""Shared test fixtures."""
import os

import pytest


@pytest.fixture(autouse=True)
def _isolated_program_disk_cache(tmp_path, monkeypatch):
    """Point the compiled-program disk cache at a per-test tmp dir.

    Keeps the suite from reading stale artifacts out of the developer's
    real ``<checkout>/.repro-cache`` (which would skip the compile+verify paths
    under test after a compiler edit) and from polluting it. Tests that
    exercise the disk cache explicitly re-monkeypatch ``REPRO_CACHE_DIR``
    themselves.

    CI opts out with ``REPRO_TEST_DISK_CACHE=1``: there the cache dir is
    keyed (actions/cache) on a hash of every compiler/core source, so a
    restored artifact is guaranteed to match the code under test and
    cold runs genuinely skip compile+verify.
    """
    if os.environ.get("REPRO_TEST_DISK_CACHE") == "1":
        yield
        return
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "repro-cache"))
    yield
