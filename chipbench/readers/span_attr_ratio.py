"""Σ of attribute ``num`` over Σ of attribute ``den`` across the
program's spans called ``span`` in the traced stretch that state both:
work done per unit of what it yielded.  No such span, or nothing in the
denominator → nothing to read."""


def read(run, span, num, den):
    tr = run.get("trace")
    if not tr:
        return None
    rows = [e["args"] for e in tr.get("spans", ())
            if e["name"] == span and num in e.get("args", {})
            and den in e.get("args", {})]
    total = sum(float(a[den]) for a in rows)
    return sum(float(a[num]) for a in rows) / total if total else None
