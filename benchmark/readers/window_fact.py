"""A number the driver took in the window (``params["fact"]``), or the
ratio of two in percent (``params["over"]``)."""


def read(params, facts):
    value = facts["window"].get(params["fact"])
    if value is None:
        return None
    if "over" in params:
        base = facts["window"].get(params["over"])
        return 100.0 * value / base if base else None
    return value
