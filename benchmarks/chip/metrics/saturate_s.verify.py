"""Seconds per verdict in e-graph saturation: the ``saturate`` entry of
each report's summed phase times, averaged over the window's verdicts."""


def read(run):
    v = [r["outcome"] for r in run.records.get("verdicts", ())
         if "phase_s" in r["outcome"]]
    return (sum(o["phase_s"].get("saturate", 0.0) for o in v) / len(v)
            if v else None)
