"""Every alloc of a completed job carries the ask its job was sent with
(`expected`: job -> (allocs asked, ask))."""


def check(snap: dict, expected: dict, config: dict) -> list[str]:
    bad_ask = 0
    for a in snap["allocs"]:
        if a["job"] not in expected:
            continue
        ask = expected[a["job"]][1]
        if a["cpu"] != ask["cpu_mhz"] or a["mem"] != ask["memory_mb"]:
            bad_ask += 1
    if bad_ask:
        return [f"{bad_ask} allocs do not carry the configuration's ask"]
    return []
