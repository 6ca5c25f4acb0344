"""emission: result rows the window's timed sends were owed (the stamp's
`owed`, what the model's `expected_rows` told the tracker — and, in a correct
run, what was delivered) over the events they carried.  The traffic and the
query fix it; the reading says what the run had: a count atom emits a row for
every collected prefix, several rows a key an event.  None without a timed
send."""


def read(run):
    stamps = [st for st in run["stamps"] if "returned" in st]
    if not stamps or not run["events"]:
        return None
    return sum(st["owed"] for st in stamps) / run["events"]
