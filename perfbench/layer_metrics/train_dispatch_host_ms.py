"""Model step: the host's time inside one ``Executor.run`` dispatch, the
mean over every step of the window (their sum spans most of a second). With
several steps in flight the device does not wait for it, so the rate no
longer shows it; a loop that reads each loss before the next dispatch pays
it every step."""


def read(records):
    train = records.get("train")
    if not train or not train.get("dispatch_seconds"):
        return None
    spent = train["dispatch_seconds"]
    return 1e3 * sum(spent) / len(spent)
