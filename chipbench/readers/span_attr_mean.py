"""Mean of attribute ``attr`` over the program's spans called ``span``
in the traced stretch."""


def read(run, span, attr):
    tr = run.get("trace")
    if not tr:
        return None
    vals = [float(e["args"][attr]) for e in tr.get("spans", ())
            if e["name"] == span and attr in e.get("args", {})]
    return sum(vals) / len(vals) if vals else None
