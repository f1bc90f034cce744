"""The profiler's trace format, ``tsl/profiler/protobuf/xplane.proto``,
as message classes of a private descriptor pool, so a trace can be read with
its op metadata (HLO category, source op) and without TensorFlow."""

from __future__ import annotations

from google.protobuf import descriptor_pool, message_factory

# The serialized FileDescriptorProto of xplane.proto (package
# tensorflow.profiler): XSpace > XPlane > XLine > XEvent, with per-plane
# event and stat metadata.
_SCHEMA = (
    b'\n"tsl/profiler/protobuf/xplane.proto'
    b'\x12\x13tensorflow.profiler"j\n\x06XSpace\x12+\n\x06p'
    b'lanes\x18\x01 \x03(\x0b2\x1b.tensorflow.profiler.XP'
    b'lane\x12\x0e\n\x06errors\x18\x02 \x03(\t\x12\x10\n\x08warnings\x18\x03 \x03'
    b'(\t\x12\x11\n\thostnames\x18\x04 \x03(\t"\xba\x03\n\x06XPlane\x12\n\n\x02'
    b'id\x18\x01 \x01(\x03\x12\x0c\n\x04name\x18\x02 \x01(\t\x12)\n\x05lines\x18\x03 \x03('
    b'\x0b2\x1a.tensorflow.profiler.XLine\x12F\n\x0eeve'
    b'nt_metadata\x18\x04 \x03(\x0b2..tensorflow.profi'
    b'ler.XPlane.EventMetadataEntry\x12D\n\rsta'
    b't_metadata\x18\x05 \x03(\x0b2-.tensorflow.profil'
    b'er.XPlane.StatMetadataEntry\x12)\n\x05stats'
    b'\x18\x06 \x03(\x0b2\x1a.tensorflow.profiler.XStat\x1aY'
    b'\n\x12EventMetadataEntry\x12\x0b\n\x03key\x18\x01 \x01(\x03\x122\n'
    b'\x05value\x18\x02 \x01(\x0b2#.tensorflow.profiler.X'
    b'EventMetadata:\x028\x01\x1aW\n\x11StatMetadataEnt'
    b'ry\x12\x0b\n\x03key\x18\x01 \x01(\x03\x121\n\x05value\x18\x02 \x01(\x0b2".ten'
    b'sorflow.profiler.XStatMetadata:\x028\x01"\xbb'
    b'\x01\n\x05XLine\x12\n\n\x02id\x18\x01 \x01(\x03\x12\x12\n\ndisplay_id\x18\n'
    b' \x01(\x03\x12\x0c\n\x04name\x18\x02 \x01(\t\x12\x14\n\x0cdisplay_name\x18\x0b'
    b' \x01(\t\x12\x14\n\x0ctimestamp_ns\x18\x03 \x01(\x03\x12\x13\n\x0bdurati'
    b'on_ps\x18\t \x01(\x03\x12+\n\x06events\x18\x04 \x03(\x0b2\x1b.tensor'
    b'flow.profiler.XEventJ\x04\x08\x05\x10\x06J\x04\x08\x06\x10\x07J\x04\x08\x07'
    b'\x10\x08J\x04\x08\x08\x10\t"\x95\x01\n\x06XEvent\x12\x13\n\x0bmetadata_id\x18\x01'
    b' \x01(\x03\x12\x13\n\toffset_ps\x18\x02 \x01(\x03H\x00\x12\x19\n\x0fnum_occ'
    b'urrences\x18\x05 \x01(\x03H\x00\x12\x13\n\x0bduration_ps\x18\x03 \x01('
    b'\x03\x12)\n\x05stats\x18\x04 \x03(\x0b2\x1a.tensorflow.profil'
    b'er.XStatB\x06\n\x04data"\xad\x01\n\x05XStat\x12\x13\n\x0bmetada'
    b'ta_id\x18\x01 \x01(\x03\x12\x16\n\x0cdouble_value\x18\x02 \x01(\x01H\x00\x12'
    b'\x16\n\x0cuint64_value\x18\x03 \x01(\x04H\x00\x12\x15\n\x0bint64_val'
    b'ue\x18\x04 \x01(\x03H\x00\x12\x13\n\tstr_value\x18\x05 \x01(\tH\x00\x12\x15\n\x0bb'
    b'ytes_value\x18\x06 \x01(\x0cH\x00\x12\x13\n\tref_value\x18\x07 \x01('
    b'\x04H\x00B\x07\n\x05value"\x8f\x01\n\x0eXEventMetadata\x12\n\n\x02i'
    b'd\x18\x01 \x01(\x03\x12\x0c\n\x04name\x18\x02 \x01(\t\x12\x14\n\x0cdisplay_nam'
    b'e\x18\x04 \x01(\t\x12\x10\n\x08metadata\x18\x03 \x01(\x0c\x12)\n\x05stats\x18\x05'
    b' \x03(\x0b2\x1a.tensorflow.profiler.XStat\x12\x10\n\x08'
    b'child_id\x18\x06 \x03(\x03">\n\rXStatMetadata\x12\n\n\x02i'
    b'd\x18\x01 \x01(\x03\x12\x0c\n\x04name\x18\x02 \x01(\t\x12\x13\n\x0bdescription'
    b'\x18\x03 \x01(\tB\x03\xf8\x01\x01b\x06proto3'
)

_POOL = descriptor_pool.DescriptorPool()
_POOL.AddSerializedFile(_SCHEMA)
XSpace = message_factory.GetMessageClass(
    _POOL.FindMessageTypeByName("tensorflow.profiler.XSpace"))


def load(path: str) -> "XSpace":
    space = XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space
