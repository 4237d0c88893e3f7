"""Decode scheduler: the scheduler's wait for a prompt's last slice, in
milliseconds a tick that made such a read — mean `wait_slice_s` over the
tick log's rows of the rate part of the window that hold one, tracing off.
The read lies BEHIND the tick's deliveries: it is wall time tick_ms_p99
counts and no decoding stream feels (the request whose prompt it is feels
it, in its first token: ttft_p95_read_ms). About one slice's device time
where the device is the slower side; near nothing where the host is. None
where the tick log lacks the column (the parent of the PR that added it)
or no tick of the window read a slice."""
from .. import harness
from . import _oncpu


def reduce(run):
    rows = _oncpu.window_ticks(run)
    if rows is None or 'wait_slice_s' not in (rows.dtype.names or ()):
        return None
    read = rows[rows['wait_slice_s'] > 0]
    if not len(read):
        return None
    harness.say('  ticks that read a prompt\'s last slice', ticks=len(read),
                of=len(rows), wait_ms=float(read['wait_slice_s'].mean()) * 1e3,
                their_wall_ms=float(read['wall_s'].mean()) * 1e3,
                their_step_wait_ms=float(read['wait_step_s'].mean()) * 1e3)
    return float(read['wait_slice_s'].mean()) * 1e3
