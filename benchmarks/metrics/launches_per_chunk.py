"""Dispatch: device launches in the window over chunks handed over.  The
configuration's ``pipeline.launches`` says where its launches are
counted."""


def read(rec):
    if not rec["chunks"]:
        return None
    return rec["launches"] / rec["chunks"]
