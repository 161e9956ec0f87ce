"""Share of the traced window in which no operation ran on the device."""


def read(params, facts):
    if not facts["trace"].devices or facts["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - facts["busy_s"] / facts["window_s"])
