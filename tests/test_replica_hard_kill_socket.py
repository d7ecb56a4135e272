"""A child replica killed for real, over dial-back TCP workers (PR 10): the
socket leg of ``replica_set.ProcessHardKill`` (the pipe leg:
tests/test_replica_process.py). A file a transport: every case starts
three child interpreters."""

import pytest

from replica_set import ProcessHardKill
from tiny_model import bundle, _no_leaked_plan  # noqa: F401


@pytest.mark.parametrize("transport", ["socket"])
class TestProcessHardKill(ProcessHardKill):
    """The socket leg."""
