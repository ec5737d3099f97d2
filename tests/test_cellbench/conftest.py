import pytest

from cellbench import run


@pytest.fixture()
def clean_env(monkeypatch):
    """The benchmark refuses the variables the suite's conftest sets."""
    for key in run.FORBIDDEN_ENV:
        monkeypatch.delenv(key, raising=False)
    # --trace 1 sets it in os.environ; setting it here makes monkeypatch
    # restore the variable after the test
    monkeypatch.setenv("MAGI_ATTENTION_PROFILE_MODE", "0")
