"""Textbook master/worker: ANY_SOURCE receives driven by Status, tag-
coded shutdown — the pattern the matching engine's wildcard path
exists for."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"   # ranks run on the host, never the chip
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np               # noqa: E402
import ompi_tpu as MPI           # noqa: E402

TAG_WORK, TAG_RESULT, TAG_STOP = 1, 2, 3

MPI.Init()
world = MPI.get_comm_world()
r, n = world.rank(), world.size
NTASK = 3 * (n - 1)

if r == 0:
    # seed one task per worker, then farm the rest to whoever answers
    next_task = 0
    for w in range(1, n):
        world.send(np.array([next_task]), dest=w, tag=TAG_WORK)
        next_task += 1
    results = {}
    while len(results) < NTASK:
        data, st = world.recv(source=MPI.ANY_SOURCE, tag=TAG_RESULT)
        results[int(data[0])] = data[1]
        if next_task < NTASK:
            world.send(np.array([next_task]), dest=st.source,
                       tag=TAG_WORK)
            next_task += 1
        else:
            world.send(np.array([0]), dest=st.source, tag=TAG_STOP)
    for t in range(NTASK):
        assert results[t] == t * t, (t, results[t])
else:
    while True:
        data, st = world.recv(source=0, tag=MPI.ANY_TAG)
        if st.tag == TAG_STOP:
            break
        task = int(data[0])
        world.send(np.array([task, task * task]), dest=0,
                   tag=TAG_RESULT)

MPI.Finalize()
print(f"OK p16_master_worker rank={r}/{n}", flush=True)
