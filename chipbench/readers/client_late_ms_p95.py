"""How late the load generator ran: from a client's reply being ready
(the server's t_done) to that client's next submit, 95th percentile,
ms.  Holds the poll interval and any wait under ``admit_cap``."""

import statistics


def read(run):
    t0, t1 = run["window"]
    late = [r["t_submit"] - r["t_ready"] for r in run["requests"]
            if t0 <= r["t_ready"] and r["t_submit"] <= t1]
    if len(late) < 2:
        return None
    return 1e3 * statistics.quantiles(late, n=20)[-1]
