"""Share of the traced idle seconds (`breakdown.idle_gaps`) that the
reduction names by one of the program's own spans (`aph.*`)."""


def read(run):
    gaps = run.trace["idle_gaps"] if run.trace else []
    total = sum(seconds for _, seconds in gaps)
    if not total:
        return None
    return sum(seconds for name, seconds in gaps
               if name.startswith("aph.")) / total * 100.0
