"""Every alloc is visible on its node's watch: the hub's index for the
node passed the alloc's commit index before the hang detector's deadline
(`snap["observed"]`, the benchmark's observer)."""


def check(snap: dict, expected: dict, config: dict) -> list[str]:
    never = int(snap["observed"].get("never_visible", 0))
    if never:
        return [f"{never} node watches never saw a commit that touched "
                "them"]
    return []
