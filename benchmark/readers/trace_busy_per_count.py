"""Device busy time of the traced window over a count the driver kept of
it (``params["count"]``: ``steps``, ``batches``), in milliseconds."""


def read(params, facts):
    n = facts["window"].get(params["count"])
    if not n or not facts["trace"].devices:
        return None
    return 1e3 * facts["busy_s"] / n
