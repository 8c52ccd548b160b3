"""Expert layer: the most loaded held expert's rows over the mean held
expert's, both summed over the window's steps and the expert layers (the
step's own counters); 1 is perfect balance."""


def read(record: dict):
    moe = record.get("moe")
    if not moe or not moe["moe_held_load_mean"]:
        return None
    return moe["moe_held_load_max"] / moe["moe_held_load_mean"]
