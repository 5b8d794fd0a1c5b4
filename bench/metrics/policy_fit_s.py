"""Host time of the session's policy build: profiling both experts on the
traffic's conditions and fitting the decision tree
(``ArchesSession.host_policies``)."""


def read(run):
    return float(run.run["policy_fit_s"])
