"""A run leaves no process behind: ``procs.stop_all`` finds, stops and reaps
what the runtime's own shutdown orphaned.  Each case runs in a process of its
own, because adopting orphans is for the life of a process."""

import subprocess
import sys

import pytest

from perfbench.harness import manifest

# argv[1]: Python that leaves processes behind; ``sh`` runs a shell line whose
# background jobs outlive the shell
SCRIPT = """
import subprocess, sys, time
from perfbench.harness import procs
procs.adopt_orphans()
sh = lambda line: subprocess.run(["sh", "-c", line], check=True)
exec(sys.argv[1])
time.sleep(0.3)
before = procs.descendants()
t0 = time.monotonic()
stopped = procs.stop_all(grace_s=0.5, limit_s=10.0)
print(len(before), len(stopped), len(procs.descendants()),
      round(time.monotonic() - t0, 2))
"""


@pytest.mark.parametrize("orphans,n_before,n_stopped", [
    ("sh('sleep 600 & sleep 600 &')", 2, 2),            # end at SIGTERM
    ("sh('(trap \"\" TERM; exec sleep 600) &')", 1, 1),   # needs SIGKILL
    ("sh('(sleep 600 & exec sleep 600) &')", 2, 2),     # a child under an orphan
    ("sh('true &')", 1, 0),                             # ended, never waited for
    ("sh('true')", 0, 0),                               # nothing left
    # this process's own resource tracker, as a dataset cell's driver has one:
    # it ignores SIGTERM; it is ended through its pipe, at once and unkilled
    ("from multiprocessing import shared_memory as m; "
     "b = m.SharedMemory(create=True, size=64); b.close(); b.unlink()", 1, 0),
])
def test_stop_all_leaves_nothing(orphans, n_before, n_stopped):
    done = subprocess.run([sys.executable, "-c", SCRIPT, orphans],
                          cwd=manifest.ROOT, capture_output=True, text=True,
                          timeout=60)
    assert done.returncode == 0, done.stderr
    before, stopped, after, seconds = done.stdout.split()
    assert (int(before), int(stopped), int(after)) == (n_before, n_stopped, 0)
    assert float(seconds) < (0.4 if n_stopped == 0 else 5.0)
    assert done.stderr == ""
    assert "sleep 600" not in subprocess.run(
        ["ps", "-eo", "args"], capture_output=True, text=True).stdout
