"""100 * sum(part) / sum(whole) over the window's requests, from the
benchmark's own request records (prompt tokens the prefix cache served
over prompt tokens sent)."""


def read(run, part: str, whole: str):
    rows = run.rec.get("requests")
    if not rows:
        return None
    total = sum(r[whole] for r in rows)
    return 100.0 * sum(r[part] for r in rows) / total if total else None
