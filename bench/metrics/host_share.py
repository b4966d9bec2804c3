"""Percent of the sweeps' seconds that the program spent on the host
around its device call: stacking the grid (`repro.stack`), copying the
final states to the host (`repro.gather`) and summarising them
(`repro.summarize`), from each sweep's `RunResult.phases`. Every sweep of
the run counts, start to end. A program without these spans gives
nothing."""

HOST = ("repro.stack", "repro.gather", "repro.summarize")


def read(run):
    phases = [getattr(sw.result, "phases", None) or {} for sw in run.sweeps]
    if not phases or not all(k in p for p in phases for k in HOST):
        return None
    host = sum(p[k] for p in phases for k in HOST)
    return 100.0 * host / sum(sw.seconds for sw in run.sweeps)
