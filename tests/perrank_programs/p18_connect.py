"""Cross-JOB dynamic process management: two independently-launched
mpirun jobs (separate coordination services) rendezvous through
Open_port/Comm_accept/Comm_connect and exchange point-to-point traffic
over the bridge intercommunicator — including non-root ranks on both
sides (root-relayed, reader-thread progress).

argv: role ('accept'|'connect') and the port rendezvous file path.
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import sys
import time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402
from ompi_tpu.core import dpm_perrank as dpm  # noqa: E402

role, port_file = sys.argv[1], sys.argv[2]

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size

if role == "accept":
    if r == 0:
        port = dpm.open_port()
        with open(port_file + ".tmp", "w") as f:
            f.write(port)
        os.rename(port_file + ".tmp", port_file)   # atomic publish
        port = world.bcast(port, root=0)
    else:
        port = world.bcast(None, root=0)
    ic = dpm.comm_accept(port, world, root=0, timeout=150)
else:
    deadline = time.monotonic() + 150   # 1-core CI: four jax
    # imports serialize before the accept side can publish
    while not os.path.exists(port_file):
        if time.monotonic() > deadline:
            raise SystemExit("port file never appeared")
        time.sleep(0.1)
    port = open(port_file).read().strip()
    ic = dpm.comm_connect(port, world, root=0, timeout=150)

assert ic.remote_size == n, ic.remote_size

# every local rank messages its same-numbered remote peer, both
# directions, including non-roots (exercises the relay both ways)
token = 100 if role == "accept" else 200
ic.send(np.array([token + r, r]), remote_rank=r, tag=7)
data, st = ic.recv(source=r, tag=7, timeout=60)
expect = (200 if role == "accept" else 100) + r
assert data[0] == expect and st.source == r, (data, st.source)

# cross-rank: local rank 0 also messages every remote rank
if r == 0:
    for rr in range(ic.remote_size):
        ic.send({"from": role, "to": rr}, remote_rank=rr, tag=8)
obj, st8 = ic.recv(source=0, tag=8, timeout=60)
assert obj["to"] == r and obj["from"] != role, obj

ic.disconnect()
if role == "accept" and r == 0:
    dpm.close_port(port)
MPI.Finalize()
print(f"OK p18_connect {role} rank={r}/{n}", flush=True)
