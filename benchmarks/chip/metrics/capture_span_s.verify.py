"""Seconds per verdict in capture and SPMD expansion: the ``capture`` and
``expand`` entries of each report's summed phase times (the checker's
``capture`` and ``expand`` spans, timed whether or not its tracer is on),
averaged over the window's verdicts.  Reports without them read nothing."""


def read(run):
    v = [r["outcome"]["phase_s"] for r in run.records.get("verdicts", ())
         if "capture" in r["outcome"].get("phase_s", {})]
    return (sum(p["capture"] + p.get("expand", 0.0) for p in v) / len(v)
            if v else None)
