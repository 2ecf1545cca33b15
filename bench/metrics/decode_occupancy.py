"""decode_occupancy: mean number of live decode slots per decode call in
the traced stretch, counted by the harness from the tokens each decode
call added."""


def read(view):
    occ = (view.record.get("traced") or {}).get("occ")
    if not occ:
        return None
    return sum(occ) / len(occ)
