"""Engine step loop: milliseconds of ``engine.step()`` on the host clock,
median over the window's steps that decoded: the admissions the step made
(each prompt's prefill and K/V insert), one batched decode over every lane,
its sampling and the host sync that reads the tokens back."""

import numpy as np


def read(record: dict):
    serve = record.get("serve")
    if not serve:
        return None
    decoded = serve["decoded_lanes"] > 0
    if not decoded.any():
        return None
    return 1e3 * float(np.median(serve["step_s"][decoded]))
