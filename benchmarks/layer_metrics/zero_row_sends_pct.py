"""served path: the share of the window's timed sends that OWED no rows (the
stamp's `owed`, what the model's `expected_rows` told the tracker: a send
whose groups all stand under `having`), as a percentage.  Such a send is done
when its call returns and its subscriber never runs; the traffic fixes the
share, the reading says the run had them.  None without a timed send."""


def read(run):
    stamps = [st for st in run["stamps"] if "returned" in st]
    if not stamps:
        return None
    return 100.0 * sum(st["owed"] == 0 for st in stamps) / len(stamps)
