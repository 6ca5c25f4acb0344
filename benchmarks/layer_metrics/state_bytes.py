"""state: bytes of device state the app holds (`rt.state_memory()`, every
owner and component summed)."""


def read(run):
    return sum(v for comps in run["state_memory"].values()
               for v in comps.values())
