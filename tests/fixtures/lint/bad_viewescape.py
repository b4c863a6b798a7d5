"""Seeded buffer-lifetime bugs: zero-copy views that escape their release
point, plus the sanctioned shape (view-travels-with-its-batch) that must
stay silent."""


def _np_column_views(batch):
    return {"c": batch}


class BadRebatcher:
    def __init__(self):
        self._pending = []
        self._stash = None

    def push(self, batch):
        views = _np_column_views(batch)
        self._stash = views  # SEED: view-escapes-release
        self._pending.append(views)  # SEED: view-escapes-release
        return views  # SEED: view-escapes-release

    def push_ok(self, batch):
        views = _np_column_views(batch)
        self._pending.append((batch, views))  # ok: travels with its batch

    def deliver_later_bad(self, batch):
        views = _np_column_views(batch)

        def deliver_later():  # SEED: view-escapes-release
            return dict(views)

        return deliver_later
