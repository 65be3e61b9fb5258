"""host_ms: host time per wave in the harness's calls into the program
(submit, dispatch, drain), less the time blocked on the device and the
copy of responses to the host; from the harness's spans in the trace."""
import tracecut


def read(run):
    if run.trace is None:
        return None
    s = tracecut.host_s_per_wave(run.trace)
    return None if s is None else s * 1e3
