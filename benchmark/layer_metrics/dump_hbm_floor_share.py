"""``dump_hbm_floor_share``: ``hbm_floor_share``'s reader under a name
of ``dump-1m.incr``'s own: the least time the chip's memory could take
over the traced backup's user bytes, as a share of the seconds an
operation actually ran on the device.  Bound: memory (one read of every
byte)."""

from benchmark.layer_metrics.hbm_floor_share import read  # noqa: F401
