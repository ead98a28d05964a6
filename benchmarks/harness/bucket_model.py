"""The plain model of one S3 bucket that the S3 cells hold the gateway
to, result for result: a dictionary of names, nothing of the program.

- a GET returns the bytes of the name's last acknowledged PUT;
- a HEAD returns that PUT's size;
- after an acknowledged DELETE a GET or a HEAD of the name is a 404.

Only acknowledged requests change the model: a request that failed, or
whose reply never came, leaves it as it was. A model is exact where one
caller owns its names (each client of a cell keeps its own), since then
no other request can land between a request and its reply.
"""

from __future__ import annotations

from dataclasses import dataclass

#: S3's status for a name the bucket does not hold
NOT_FOUND = 404


@dataclass(frozen=True)
class Answer:
    """What a request should get back: a status, and for a GET the
    bytes, for a HEAD the size."""

    status: int
    body: object = None
    size: int = 0


class BucketModel:
    def __init__(self):
        self._objects: dict[str, object] = {}
        self._deleted: set[str] = set()

    # what acknowledged requests do
    def put(self, name: str, data) -> None:
        """An acknowledged PUT of `data` (any buffer with `len`; kept by
        reference, never copied)."""
        self._objects[name] = data
        self._deleted.discard(name)

    def delete(self, name: str) -> None:
        """An acknowledged DELETE (of a name held or not: S3 answers 204
        either way)."""
        if self._objects.pop(name, None) is not None:
            self._deleted.add(name)

    # what requests should get back
    def get(self, name: str) -> Answer:
        if name not in self._objects:
            return Answer(NOT_FOUND)
        data = self._objects[name]
        return Answer(200, body=data, size=len(data))

    def head(self, name: str) -> Answer:
        if name not in self._objects:
            return Answer(NOT_FOUND)
        return Answer(200, size=len(self._objects[name]))

    # what the bucket holds
    def live(self) -> list[str]:
        """The names held, in the order of their first acknowledged PUT."""
        return list(self._objects)

    def deleted(self) -> list[str]:
        """Names whose last acknowledged request was a DELETE."""
        return sorted(self._deleted)
