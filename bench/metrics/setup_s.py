"""Set-up: process start to the first timed slot (host clock).

Weights, session, the policy's profile-and-fit, compilation or the
persistent cache's load, and the warm-up slots."""


def read(run):
    return float(run.run["setup_s"])
