"""Train runtime: host clock around ``train.report`` in the loop, mean
over the window's steps."""


def read(obs):
    m = obs.get("train")
    if not m or not m["report_s"]:
        return None
    return 1000.0 * sum(m["report_s"]) / len(m["report_s"])
