"""The uplink codec "none": the identity, the server trains on the upload
as computed."""


def roundtrip(x, key):
    return x
