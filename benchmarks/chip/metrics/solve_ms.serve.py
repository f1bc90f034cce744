"""Mean wall of ``AdmissionController.admit`` per round (benchmark span)."""


def read(rec):
    s = rec["spans"].get("admit")
    return sum(s) / len(s) * 1e3 if s else None
