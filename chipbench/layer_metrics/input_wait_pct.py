"""Train runtime / data: time the loop waited for its next batch
(``DeviceFeed``'s ``consumer_starve_s``) over the window."""


def read(obs):
    m = obs.get("train")
    if not m:
        return None
    return 100.0 * m["starve_in_window_s"] / obs["seconds"]
