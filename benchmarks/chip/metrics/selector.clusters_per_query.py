"""Mean number of clusters the Stage-II selector selects per query, over
the window's answers that `correct` compares: the selection of the
reference that each answer matched."""


def read(ctx):
    return ctx.selection.get("clusters_per_query")
