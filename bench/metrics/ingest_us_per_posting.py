"""Index write path (``engine/engine.py`` ``add_documents``): host time per
posting of the set-up ingest, in us: the ``repro.engine.ingest`` span
total at the window's start (``EngineStats.spans``) over the postings
the index held then.  Host ingest is about half of a warm run's set-up;
moves ``setup_s``.  Nothing to read without the span table."""


def read(run):
    table = run.before.get("spans")
    if not table or "repro.engine.ingest" not in table:
        return None
    postings = run.before["num_postings"]
    return 1e6 * table["repro.engine.ingest"][1] / postings if postings \
        else None
