"""Expert layer: share of the router's assignments that fall on experts
held here, over the window's steps and the expert layers (the step's own
counters), in percent; 100 x held / published experts when the router is
balanced."""


def read(record: dict):
    moe = record.get("moe")
    if not moe or not moe["moe_assignments"]:
        return None
    return 100.0 * moe["moe_assignments_held"] / moe["moe_assignments"]
