"""Host milliseconds per simulated second outside the assessment tick:
(window wall - window ``Simulation.assess_wall``) / simulated seconds."""


def read(run):
    c = run.counters
    if not c.get("sim_s"):
        return None
    return (c["window_wall_s"] - c["assess_wall_s"]) / c["sim_s"] * 1e3
