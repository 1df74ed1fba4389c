"""Device time per traced step inside host-transfer operations with no
`hostcb.*` span open and no operation running, ms: the link and the
runtime's hand-off around each host callback (bench/callbacks.py)."""
from bench import runstate


def read(run):
    split = runstate.callback_split(run)
    if split is None:
        return None
    return 1e3 * split["hostcb_xfer_s"] / run.traced_steps
