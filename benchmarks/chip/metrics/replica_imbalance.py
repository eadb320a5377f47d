"""Router: the most tokens served by one replica over the mean over the
replicas, from each engine's counters between the window's start and the
loop's stop (a decode step's token each, and the prefill's first); 1 is
an even spread."""


def read(run):
    per = [c.get("tokens", 0) + c.get("prefills", 0)
           for c in run.record.replica_counters]
    if not per or not sum(per):
        return None
    return max(per) / (sum(per) / len(per))
