"""Records the small trace kept as ``tests/data/small.xplane.pb``: three
runs of one jitted matrix product on the chip, 50 ms of host sleep apart.
Run on the chip once (PR 24); the test reads the recording."""

import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmark import tracered  # noqa: E402

out = Path(sys.argv[1])
out.mkdir(parents=True, exist_ok=True)
x = jnp.ones((2048, 2048), jnp.bfloat16)
step = jax.jit(lambda a: (a @ a).astype(jnp.bfloat16))
step(x).block_until_ready()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 1
t0 = time.monotonic()
jax.profiler.start_trace(str(out / "trace"), profiler_options=opts)
for _ in range(3):
    step(x).block_until_ready()
    time.sleep(0.05)
jax.profiler.stop_trace()
wall = time.monotonic() - t0
path = tracered.find_xplane(str(out / "trace"))
Path(out / "small.xplane.pb").write_bytes(Path(path).read_bytes())
print(wall, tracered.reduce_xplane(path, wall, phase="fixture"))
