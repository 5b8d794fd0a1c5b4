"""Slot-UEs completed per second: every UE of every slot completed in the
window, over the window's seconds (host clock)."""


def read(run):
    w = run.window
    if w is None or w.n_timed == 0:
        return None
    n_ues = run.timed_outputs("kpms/oai/snr").shape[1]
    return w.n_timed * n_ues / w.seconds
