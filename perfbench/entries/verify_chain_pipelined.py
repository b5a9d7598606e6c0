"""Entry adapter: ``verify_chain`` through the node's default, pipelined
provider (``provider=None``), as ``lightserve`` calls it. No shipped
deployment names it: on the chip its bundles, and so the shapes it
compiles, change from request to request (PERF.md, Open questions). It is
here to read that again, and for a deployment's file to name once the
program bundles steadily."""

from perfbench.entries import verify_chain


class Entry(verify_chain.Entry):
    PIPELINED = True
