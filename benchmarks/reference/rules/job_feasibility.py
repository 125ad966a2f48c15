"""Every alloc sits on a node in one of its job's datacenters whose
attributes meet the job's `=` constraints; judged once per (job, node)
pair that occurs."""


def _attr_of(node: dict, target: str):
    if target.startswith("${attr.") and target.endswith("}"):
        return node["attributes"].get(target[7:-1])
    if target == "${node.datacenter}":
        return node["datacenter"]
    return None


def check(snap: dict, expected: dict, config: dict) -> list[str]:
    faults = []
    by_id = {n["id"]: n for n in snap["nodes"]}
    bad_dc = bad_constraint = 0
    for job_id, node_id in {(a["job"], a["node"]) for a in snap["allocs"]}:
        job, node = snap["jobs"].get(job_id), by_id.get(node_id)
        if job is None or node is None:
            continue
        if node["datacenter"] not in job["datacenters"]:
            bad_dc += 1
        for ltarget, operand, rtarget in job["constraints"]:
            if operand == "=" and _attr_of(node, ltarget) != rtarget:
                bad_constraint += 1
    if bad_dc:
        faults.append(f"{bad_dc} (job, node) placements are outside the "
                      "job's datacenters")
    if bad_constraint:
        faults.append(f"{bad_constraint} (job, node) placements break a "
                      "constraint of the job")
    return faults
