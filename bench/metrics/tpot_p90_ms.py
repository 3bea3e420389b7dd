"""90th percentile over requests of the mean time per output token after
the first: ``(finished − first token) / (tokens − 1)``, in ms.  Requests
due in the window that produced two tokens or more."""

from bench.generator import percentile


def read(run):
    recs = [r for r in run.data.get("records", ())
            if r["first"] is not None and r["finished"] is not None and len(r["tokens"]) > 1]
    if not recs:
        return None
    return 1e3 * percentile([(r["finished"] - r["first"]) / (len(r["tokens"]) - 1)
                             for r in recs], 90)
