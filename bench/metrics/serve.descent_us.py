"""Device time of one forest descent (`core/forest.py` `_forest_predict`,
driven by `serve/engine.py` `ForestServer.predict`): the program's device
seconds in the trace over its executions."""

NAME = "forest_predict"


def read(run):
    s = run.get("trace")
    if run["kind"] != "serve" or s is None:
        return None
    hits = [v for k, v in s["modules"].items() if NAME in k]
    count = sum(v["count"] for v in hits)
    if count == 0:
        return None
    return 1e6 * sum(v["seconds"] for v in hits) / count
