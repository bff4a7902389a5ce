"""peak_bytes_in_use / bytes_limit after the window, on the fullest chip."""


def read(spec: dict, ctx: dict):
    shares = [
        m["peak_bytes_in_use"] / m["bytes_limit"]
        for m in ctx.get("memory") or []
        if m.get("bytes_limit")
    ]
    return max(shares) * 100.0 if shares else None
