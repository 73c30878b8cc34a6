"""serve.batch_fill: mean share of a micro-batch's slots that held a
request, over the window's dispatches (AdmissionQueue stats), in %."""


def read(run):
    if not run.counters.get("dispatches"):
        return None
    return 100.0 * run.counters["occupancy_mean"]
