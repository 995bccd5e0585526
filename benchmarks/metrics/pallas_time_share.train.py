"""kernels: device self time of the hand-written Pallas kernels' events
(told from XLA's own ops by ``custom_call_target="tpu_custom_call"`` in the
event's HLO text, lib/trace.py) over device busy time."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    return 100.0 * trace["kernel_s"] / trace["busy_s"]
