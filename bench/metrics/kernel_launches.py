"""Pallas kernel launches in one step's jaxpr (the program's dispatch
audit, ``repro.analysis.audit_report``): a count, not a time."""


def read(rec):
    audit = rec.get("audit")
    if audit is None:
        return None
    return sum(audit["kernel_launches"].values())
