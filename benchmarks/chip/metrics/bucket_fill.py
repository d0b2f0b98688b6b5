"""Rows the backend's steps served over the rows they computed, in
percent: 100 x sum of active rows / sum of padded bucket rows over the
program's ``backend.step`` spans.  Padding is work that serves no request;
it should move ``req_per_s``."""

from chip import program_spans


def read(run):
    return program_spans.fill_pct(run)
