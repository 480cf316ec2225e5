"""The Python daemon as an operator runs it: real subprocesses started from
the CLI (`python -m oncilla_tpu.runtime.daemon NODEFILE --rank N`), the
deployment shape of the reference's `bin/oncillamem nodefile`
(/root/reference/src/main.c:187-221), including SIGTERM teardown."""

import os
import signal
import subprocess
import sys

import numpy as np

from _helpers import free_ports, wait_nnodes, wait_port
from oncilla_tpu.runtime.client import ControlPlaneClient
from oncilla_tpu.runtime.membership import NodeEntry
from oncilla_tpu.utils.config import OcmConfig
from oncilla_tpu import OcmKind


def test_daemon_cli_cluster_and_sigterm(tmp_path, rng):
    ports = free_ports(2)
    nodefile = tmp_path / "nodefile"
    nodefile.write_text(
        "".join(f"{r} 127.0.0.1 {p}\n" for r, p in enumerate(ports))
    )
    # A platform that does not exist: a daemon only books device extents
    # (ArenaAllocator), so it must never initialise a JAX backend — if it
    # did, it would raise here, and on a TPU host it would take the chip
    # from the one process that may hold it.
    env = dict(os.environ, JAX_PLATFORMS="no_such_platform")
    logs = [open(tmp_path / f"daemon{r}.log", "wb") for r in range(2)]
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "oncilla_tpu.runtime.daemon",
             str(nodefile), "--rank", str(r)],
            env=env, stdout=logs[r], stderr=subprocess.STDOUT,
        )
        for r in range(2)
    ]

    def diagnostics() -> str:
        return "\n".join(
            (tmp_path / f"daemon{r}.log").read_text(errors="replace")
            for r in range(2)
        )

    try:
        for p in ports:
            assert wait_port(p), f"daemon did not come up:\n{diagnostics()}"
        # A listening socket does not imply the cluster formed; wait for the
        # ADD_NODE join so the alloc cannot hit a 1-node demotion.
        assert wait_nnodes(ports[0], 2), (
            f"cluster never formed:\n{diagnostics()}"
        )
        entries = [NodeEntry(r, "127.0.0.1", p) for r, p in enumerate(ports)]
        cfg = OcmConfig(heartbeat_s=0.2)
        client = ControlPlaneClient(entries, 0, config=cfg)
        h = client.alloc(64 << 10, OcmKind.REMOTE_HOST)
        assert h.rank == 1
        data = rng.integers(0, 256, 64 << 10, dtype=np.uint8)
        client.put(h, data, 0)
        np.testing.assert_array_equal(
            np.asarray(client.get(h, 64 << 10, 0)), data
        )
        client.free(h)
        hd = client.alloc(64 << 10, OcmKind.REMOTE_DEVICE)
        assert hd.rank == 1
        client.free(hd)
        client.close()
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        rcs = []
        for p in procs:
            try:
                rcs.append(p.wait(timeout=15))
            except subprocess.TimeoutExpired:
                p.kill()
                rcs.append("killed")
        for f in logs:
            f.close()
    assert rcs == [0, 0], f"SIGTERM exit codes {rcs}:\n{diagnostics()}"
