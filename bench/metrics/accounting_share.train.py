"""Share of a metered training window that the energy accounting costs:
1 - wall(the same number of steps unmetered) / wall(metered, with the bill),
both on the host clock in the traced run, neither of them traced."""


def read(facts):
    if facts.get("kind") != "train" or "unmetered_window_s" not in facts:
        return None
    return 100.0 * (1.0 - facts["unmetered_window_s"] / facts["window_s"])
