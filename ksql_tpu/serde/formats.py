"""Serde formats: value/key (de)serialization.

Analog of ksqldb-serde (Format.java:41, FormatFactory.java:51,
GenericRowSerDe/GenericKeySerDe).  Formats implemented natively: JSON,
DELIMITED (CSV), KAFKA (primitive binary), NONE.  AVRO/PROTOBUF/JSON_SR
currently alias to schema'd JSON (documented deviation: the wire format
differs but the logical row round-trip is exact; a real schema-registry
format can slot in behind the same interface).
"""

from __future__ import annotations

import base64
import decimal as _decimal
import json
import math
import re
import struct
from typing import Any, Callable, Dict, List, Optional, Tuple

from ksql_tpu.common import faults
from ksql_tpu.common.errors import SerdeException
from ksql_tpu.common.schema import Column, LogicalSchema
from ksql_tpu.common.types import SqlBaseType, SqlType


class Format:
    name = "NONE"

    def serialize(self, row: Optional[Dict[str, Any]], columns: List[Column]) -> Any:
        raise NotImplementedError

    def deserialize(self, payload: Any, columns: List[Column]) -> Optional[Dict[str, Any]]:
        raise NotImplementedError


def _coerce(value: Any, t: SqlType) -> Any:
    """Coerce a JSON-decoded value into the SQL type's host representation."""
    if value is None:
        return None
    b = t.base
    if b == SqlBaseType.BOOLEAN:
        if isinstance(value, bool):
            return value
        if isinstance(value, str):
            return value.lower() == "true"
        return bool(value)
    if b in (SqlBaseType.INTEGER, SqlBaseType.BIGINT):
        if isinstance(value, bool):
            raise SerdeException(f"cannot coerce boolean to {t}")
        if isinstance(value, float):
            # Connect's Number.intValue()/longValue(): truncate toward zero
            return int(value)
        return int(value)
    if b in (SqlBaseType.DOUBLE,):
        if isinstance(value, bool):
            raise SerdeException(f"cannot coerce boolean to {t}")
        return float(value)
    if b == SqlBaseType.DECIMAL:
        if isinstance(value, bool):
            raise SerdeException(f"cannot coerce boolean to {t}")
        try:
            d = (
                value
                if isinstance(value, _decimal.Decimal)
                else _decimal.Decimal(
                    repr(value) if isinstance(value, float) else str(value)
                )
            )
        except _decimal.InvalidOperation:
            raise SerdeException(f"cannot coerce {value!r} to {t}") from None
        quantum = _decimal.Decimal(1).scaleb(-(t.scale or 0))
        return d.quantize(quantum, rounding=_decimal.ROUND_HALF_UP)
    if b == SqlBaseType.STRING:
        if isinstance(value, bool):
            return "true" if value else "false"
        if isinstance(value, (dict, list)):
            return json.dumps(value, separators=(",", ":"))
        return str(value)
    if b == SqlBaseType.BYTES:
        if isinstance(value, bytes):
            return value
        return base64.b64decode(value)
    if b == SqlBaseType.TIMESTAMP:
        if isinstance(value, str):
            if re.fullmatch(r"-?\d+", value.strip()):
                return int(value)  # epoch-ms rendered as text (Avro/Connect)
            from ksql_tpu.execution.interpreter import _parse_timestamp_text

            return _parse_timestamp_text(value)
        return int(value)
    if b == SqlBaseType.DATE:
        if isinstance(value, str):
            if re.fullmatch(r"-?\d+", value.strip()):
                return int(value)  # epoch-days rendered as text
            from ksql_tpu.execution.interpreter import _parse_date_text

            return _parse_date_text(value)
        return int(value)
    if b == SqlBaseType.TIME:
        if isinstance(value, str):
            if re.fullmatch(r"-?\d+", value.strip()):
                return int(value)  # ms-of-day rendered as text
            from ksql_tpu.execution.interpreter import _parse_time_text

            return _parse_time_text(value)
        return int(value)
    if b == SqlBaseType.ARRAY:
        if not isinstance(value, list):
            raise SerdeException(f"cannot coerce {type(value).__name__} to {t}")
        return [_coerce(v, t.element) for v in value]
    if b == SqlBaseType.MAP:
        if not isinstance(value, dict):
            raise SerdeException(f"cannot coerce {type(value).__name__} to {t}")
        return {k: _coerce(v, t.element) for k, v in value.items()}
    if b == SqlBaseType.STRUCT:
        if not isinstance(value, dict):
            raise SerdeException(f"cannot coerce {type(value).__name__} to {t}")
        fields = dict(t.fields or ())
        lower = {k.upper(): v for k, v in value.items()}
        return {name: _coerce(lower.get(name.upper()), ft) for name, ft in fields.items()}
    raise SerdeException(f"unsupported type {t}")


def decimal_str(v: Any, t: SqlType) -> str:
    """Plain fixed-point rendering at the column's scale (the reference
    serializes BigDecimal.toPlainString — no zero-padding of the integer
    part, e.g. DECIMAL(5,3) 1 -> "1.000")."""
    scale = t.scale or 0
    return f"{v:.{scale}f}" if scale else str(int(v))


def _jsonable(value: Any, t: Optional[SqlType] = None, decimal_as_string: bool = False) -> Any:
    if value is None:
        return None
    if isinstance(value, bytes):
        return base64.b64encode(value).decode("ascii")
    if (
        t is not None
        and t.base == SqlBaseType.DECIMAL
        and isinstance(value, _decimal.Decimal)
        and value.adjusted() + 1 > (t.precision or 38) - (t.scale or 0)
        and value != 0
    ):
        # aggregate values past the declared precision fail the query, as
        # BigDecimal.setScale/DecimalUtil.ensureFit does (sum overflow)
        raise SerdeException(
            f"Numeric field overflow: value {value} does not fit {t}"
        )
    if (
        decimal_as_string
        and t is not None
        and t.base == SqlBaseType.DECIMAL
        and isinstance(value, (int, float, _decimal.Decimal))
        and not isinstance(value, bool)
    ):
        return decimal_str(value, t)
    if isinstance(value, _decimal.Decimal):
        # plain-JSON decimals emit as numbers (double range)
        return int(value) if value == value.to_integral_value() and (t is None or (t.scale or 0) == 0) else float(value)
    if isinstance(value, float):
        # Jackson writes non-finite doubles as NaN/Infinity tokens; QTT
        # expected files carry them as strings
        if value != value:
            return "NaN"
        if value == float("inf"):
            return "Infinity"
        if value == float("-inf"):
            return "-Infinity"
        return value
    if isinstance(value, dict):
        if t is not None and t.base == SqlBaseType.STRUCT:
            fts = dict(t.fields or ())
            return {k: _jsonable(v, fts.get(k), decimal_as_string)
                    for k, v in value.items()}
        et = t.element if t is not None and t.base == SqlBaseType.MAP else None
        return {k: _jsonable(v, et, decimal_as_string) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        et = t.element if t is not None and t.base == SqlBaseType.ARRAY else None
        return [_jsonable(v, et, decimal_as_string) for v in value]
    return value


class JsonFormat(Format):
    name = "JSON"
    decimal_as_string = False  # AVRO renders decimals as padded strings

    def __init__(self, wrap: bool = True):
        # wrap=False = SerdeFeature.UNWRAP_SINGLES: a single column is
        # (de)serialized as the bare value, no envelope (SerdeUtils.java:63)
        self.wrap = wrap

    def serialize(self, row, columns):
        if row is None:
            return None
        das = self.decimal_as_string
        if not self.wrap and len(columns) == 1:
            return json.dumps(
                _jsonable(row.get(columns[0].name), columns[0].type, das),
                separators=(",", ":"),
            )
        return json.dumps(
            {c.name: _jsonable(row.get(c.name), c.type, das) for c in columns},
            separators=(",", ":"),
        )

    def deserialize(self, payload, columns):
        if payload is None:
            return None
        if isinstance(payload, (str, bytes, bytearray)):
            try:
                obj = json.loads(payload)
            except ValueError:
                if (
                    not self.wrap
                    and len(columns) == 1
                    and columns[0].type.base == SqlBaseType.STRING
                ):
                    # unwrapped single string values arrive as raw text
                    obj = payload if isinstance(payload, str) else payload.decode()
                else:
                    raise
        else:
            obj = payload
        if not self.wrap and len(columns) == 1:
            return {columns[0].name: _coerce(obj, columns[0].type)}
        if not isinstance(obj, dict):
            # single-column anonymous value
            if len(columns) == 1:
                return {columns[0].name: _coerce(obj, columns[0].type)}
            raise SerdeException(f"expected JSON object, got {type(obj).__name__}")
        upper = {k.upper(): v for k, v in obj.items()}
        return {c.name: _coerce(upper.get(c.name.upper()), c.type) for c in columns}


class AvroFormat(JsonFormat):
    """AVRO in two tiers:

    * registry-wired **binary** tier: with a schema registry + subject the
      serde writes real Confluent-framed Avro binary (magic 0 + schema id +
      avro binary body, serde/avro_binary.py) and reads framed payloads
      back through the registry by id — the byte-level analog of
      ksqldb-serde/.../avro/AvroFormat.java + AvroConverter;
    * logical tier (no registry): JSON envelope with Avro's decimal
      rendering (fixed-scale padded strings), which is what the in-process
      QTT topics carry.

    deserialize() auto-detects framing, so both tiers coexist on a topic.
    """

    name = "AVRO"
    decimal_as_string = True

    def __init__(self, wrap: bool = True, registry=None, subject: Optional[str] = None):
        super().__init__(wrap)
        self.registry = registry
        self.subject = subject

    _writer_cache: Optional[Tuple[int, Any]] = None

    def _writer_schema(self, columns):
        import json as _json

        from ksql_tpu.serde import avro_binary as ab

        if self._writer_cache is not None:
            return self._writer_cache  # one registration per serde instance
        reg = self.registry.latest(self.subject) if self.subject else None
        if reg is not None and reg.schema_type == "AVRO":
            schema = reg.schema
            if isinstance(schema, str):
                schema = _json.loads(schema)
            self._writer_cache = (reg.schema_id, schema)
        else:
            schema = ab.sql_to_avro_schema(columns)
            sid = self.registry.register(
                self.subject or "anonymous-value", "AVRO", schema
            )
            self._writer_cache = (sid, schema)
        return self._writer_cache

    def serialize(self, row, columns):
        if self.registry is None:
            return super().serialize(row, columns)
        if row is None:
            return None
        from ksql_tpu.serde import avro_binary as ab

        sid, schema = self._writer_schema(columns)
        value = {c.name: row.get(c.name) for c in columns}
        if not self.wrap and len(columns) == 1:
            value = value[columns[0].name]
        return ab.frame(sid, ab.encode(schema, value))

    def deserialize(self, payload, columns):
        from ksql_tpu.serde import avro_binary as ab

        if self.registry is not None and ab.is_framed(payload):
            import json as _json

            sid, body = ab.unframe(bytes(payload))
            reg = self.registry.get_by_id(sid)
            if reg is None:
                raise SerdeException(f"unknown schema id {sid}")
            schema = reg.schema
            if isinstance(schema, str):
                schema = _json.loads(schema)
            obj = ab.decode(schema, body)
            if not self.wrap and len(columns) == 1:
                return {columns[0].name: _coerce(obj, columns[0].type)}
            if not isinstance(obj, dict):
                if len(columns) == 1:
                    return {columns[0].name: _coerce(obj, columns[0].type)}
                raise SerdeException(
                    f"expected Avro record, got {type(obj).__name__}"
                )
            upper = {k.upper(): v for k, v in obj.items()}
            return {c.name: _coerce(upper.get(c.name.upper()), c.type) for c in columns}
        return super().deserialize(payload, columns)


class DelimitedFormat(Format):
    name = "DELIMITED"

    def __init__(self, delimiter: str = ","):
        self.delimiter = delimiter

    def serialize(self, row, columns):
        if row is None:
            return None
        parts = []
        for i, c in enumerate(columns):
            v = row.get(c.name)
            if v is None:
                parts.append("")
            elif isinstance(v, bool):
                parts.append(self._quote("true" if v else "false", i == 0))
            elif isinstance(v, bytes):
                parts.append(self._quote(base64.b64encode(v).decode("ascii"), i == 0))
            elif (
                isinstance(v, (float, int, _decimal.Decimal))
                and c.type.base == SqlBaseType.DECIMAL
            ):
                parts.append(self._quote(decimal_str(v, c.type), i == 0))
            elif isinstance(v, float):
                from ksql_tpu.execution.interpreter import java_double_str

                parts.append(self._quote(java_double_str(v), i == 0))
            else:
                parts.append(self._quote(str(v), i == 0))
        return self.delimiter.join(parts)

    def _quote(self, s: str, first_field: bool) -> str:
        """commons-csv QuoteMode.MINIMAL quoting (the reference's CSVPrinter):
        quote on embedded delimiter/quote/newline; the first field of a record
        is also quoted when it starts with a non-alphanumeric character, other
        fields when their first character is <= '#'."""
        needs = self.delimiter in s or '"' in s or "\n" in s or "\r" in s
        if not needs:
            if not s:
                needs = first_field  # empty first field prints as ""
            else:
                ch = s[0]
                if first_field:
                    needs = not (ch.isascii() and ch.isalnum())
                else:
                    needs = ch <= "#"
                needs = needs or s[-1] <= " "  # trailing whitespace
        if needs:
            return '"' + s.replace('"', '""') + '"'
        return s

    def deserialize(self, payload, columns):
        if payload is None:
            return None
        text = payload.decode() if isinstance(payload, bytes) else str(payload)
        values = self._split(text)
        if len(values) != len(columns):
            raise SerdeException(
                f"Unexpected field count, csv line has {len(values)} columns, "
                f"schema has {len(columns)}"
            )
        out = {}
        for c, raw in zip(columns, values):
            if raw == "":
                out[c.name] = None
                continue
            b = c.type.base
            if b == SqlBaseType.BOOLEAN:
                out[c.name] = raw.strip().lower() == "true"
            elif b in (SqlBaseType.INTEGER, SqlBaseType.BIGINT):
                out[c.name] = int(raw)
            elif b == SqlBaseType.DOUBLE:
                out[c.name] = float(raw)
            elif b == SqlBaseType.DECIMAL:
                out[c.name] = _coerce(raw, c.type)
            elif b == SqlBaseType.STRING:
                out[c.name] = raw
            elif b == SqlBaseType.BYTES:
                out[c.name] = base64.b64decode(raw)
            elif b in (SqlBaseType.TIMESTAMP, SqlBaseType.DATE, SqlBaseType.TIME):
                out[c.name] = _coerce(raw if not raw.lstrip("-").isdigit() else int(raw), c.type)
            else:
                raise SerdeException(f"DELIMITED does not support type {c.type}")
        return out

    def _split(self, text: str) -> List[str]:
        out, cur, i, n = [], [], 0, len(text)
        in_quotes = False
        while i < n:
            ch = text[i]
            if in_quotes:
                if ch == '"':
                    if i + 1 < n and text[i + 1] == '"':
                        cur.append('"')
                        i += 2
                        continue
                    in_quotes = False
                else:
                    cur.append(ch)
            elif ch == '"':
                in_quotes = True
            elif ch == self.delimiter:
                out.append("".join(cur))
                cur = []
            else:
                cur.append(ch)
            i += 1
        out.append("".join(cur))
        return out


class KafkaFormat(Format):
    """Primitive binary format (KAFKA serde: int/bigint/double/string)."""

    name = "KAFKA"

    def serialize(self, row, columns):
        if row is None:
            return None
        if len(columns) != 1:
            # multi-column KAFKA keys serialize as a tuple of python values
            return tuple(row.get(c.name) for c in columns)
        v = row.get(columns[0].name)
        if v is None:
            return None
        b = columns[0].type.base
        # the in-process log carries native python values; the KAFKA format's
        # fixed-width binary encoding is applied only at a real wire boundary
        if b == SqlBaseType.INTEGER:
            return int(v)
        if b in (SqlBaseType.BIGINT, SqlBaseType.TIMESTAMP):
            return int(v)
        if b == SqlBaseType.DOUBLE:
            return float(v)
        if b in (SqlBaseType.STRING, SqlBaseType.BYTES):
            return v
        raise SerdeException(f"KAFKA format does not support {columns[0].type}")

    def deserialize(self, payload, columns):
        if payload is None:
            return None
        if isinstance(payload, tuple):
            return {c.name: v for c, v in zip(columns, payload)}
        if len(columns) != 1:
            raise SerdeException("KAFKA format supports single-column payloads")
        c = columns[0]
        b = c.type.base
        if isinstance(payload, (int, float, str, bool, list, dict)):
            # already-decoded (in-process producer path)
            return {c.name: _coerce(payload, c.type)}
        if b == SqlBaseType.INTEGER:
            return {c.name: struct.unpack(">i", payload)[0]}
        if b in (SqlBaseType.BIGINT, SqlBaseType.TIMESTAMP):
            return {c.name: struct.unpack(">q", payload)[0]}
        if b == SqlBaseType.DOUBLE:
            return {c.name: struct.unpack(">d", payload)[0]}
        if b == SqlBaseType.STRING:
            return {c.name: payload.decode("utf-8")}
        if b == SqlBaseType.BYTES:
            return {c.name: payload}
        raise SerdeException(f"KAFKA format does not support {c.type}")


def _proto3_default(v: Any, t: SqlType) -> Any:
    """proto3 scalars have no null: absent fields read back as their default
    (0 / "" / false / [] / {}); message-typed fields (struct, temporal and
    decimal well-knowns) stay null."""
    b = t.base
    if v is None:
        if b in (SqlBaseType.INTEGER, SqlBaseType.BIGINT):
            return 0
        if b == SqlBaseType.DOUBLE:
            return 0.0
        if b == SqlBaseType.BOOLEAN:
            return False
        if b == SqlBaseType.STRING:
            return ""
        if b == SqlBaseType.BYTES:
            # connect's protobuf translator maps bytes to optional -> null
            return None
        if b == SqlBaseType.ARRAY:
            return []
        if b == SqlBaseType.MAP:
            return {}
        return None
    if b == SqlBaseType.ARRAY:
        return [_proto3_default(x, t.element) for x in v]
    if b == SqlBaseType.MAP:
        return {k: _proto3_default(x, t.element) for k, x in v.items()}
    if b == SqlBaseType.STRUCT:
        fields = dict(t.fields or ())
        return {n: _proto3_default(v.get(n), ft) for n, ft in fields.items()}
    return v


class ProtobufFormat(JsonFormat):
    """PROTOBUF in two tiers (mirroring AvroFormat):

    * registry-wired **binary** tier: with a schema registry + subject the
      serde writes real Confluent-framed protobuf wire bytes (magic 0 +
      schema id + message-index path + proto3 body, serde/proto_binary.py)
      and reads framed payloads back through the registry by id — the
      byte-level analog of ksqldb-serde/.../protobuf/ProtobufFormat.java:31
      + ProtobufConverter;
    * logical tier (no registry): JSON envelope with proto3 default-value
      semantics, which is what the in-process QTT topics carry.

    ``nullable_all`` models VALUE_PROTOBUF_NULLABLE_REPRESENTATION
    (OPTIONAL/WRAPPER): scalar fields become nullable instead of defaulting
    (wrapper types on the wire).  ``float32`` lists fields whose wire type
    is single-precision ``float``: their values round-trip through float32.
    """

    name = "PROTOBUF"

    def __init__(self, wrap: bool = True, nullable_all: bool = False,
                 float32: tuple = (), registry=None, subject: Optional[str] = None,
                 full_name: Optional[str] = None):
        super().__init__(wrap)
        self.nullable_all = nullable_all
        self.float32 = frozenset(float32)
        self.registry = registry
        self.subject = subject
        self.full_name = full_name

    def _f32(self, out):
        if out and self.float32:
            for name in self.float32:
                for k in out:
                    if k.upper() == name.upper() and out[k] is not None:
                        out[k] = struct.unpack("<f", struct.pack("<f", float(out[k])))[0]
        return out

    # codec construction parses .proto text: cache per writer subject and
    # per reader schema id (this is the per-record serde hot path)
    _writer_cache: Optional[Tuple[int, Any, Tuple[int, ...]]] = None
    _reader_cache: Optional[Tuple[int, Any]] = None

    def _writer_codec(self, columns):
        from ksql_tpu.serde import proto_binary as pb

        if self._writer_cache is not None:
            return self._writer_cache
        reg = self.registry.latest(self.subject) if self.subject else None
        if reg is not None and reg.schema_type == "PROTOBUF":
            codec = pb.codec_for_text(
                str(reg.schema),
                tuple(str(r) for r in reg.references if r),
                self.full_name,
            )
            # frame with the root's index among the schema's declared
            # messages — a root that is not the first top-level message
            # must not be framed as ([0]) or registry-faithful consumers
            # decode the wrong type
            indexes = pb.message_index_path(str(reg.schema), codec.root)
            self._writer_cache = (reg.schema_id, codec, indexes)
        else:
            text, messages = pb.sql_to_proto_schema(
                columns, nullable_all=self.nullable_all
            )
            sid = self.registry.register(
                self.subject or "anonymous-value", "PROTOBUF", text
            )
            self._writer_cache = (
                sid, pb.ProtoCodec(messages, "ConnectDefault1"), (0,)
            )
        return self._writer_cache

    def serialize(self, row, columns):
        if row is None:
            return None
        if self.registry is not None:
            from ksql_tpu.serde import proto_binary as pb

            sid, codec, indexes = self._writer_codec(columns)
            value = {c.name: row.get(c.name) for c in columns}
            if not self.nullable_all:
                value = {
                    c.name: _proto3_default(value.get(c.name), c.type)
                    for c in columns
                }
            return pb.frame(sid, codec.encode(value), indexes)
        if not self.nullable_all:
            row = {c.name: _proto3_default(row.get(c.name), c.type) for c in columns}
        return super().serialize(row, columns)

    def deserialize(self, payload, columns):
        from ksql_tpu.serde import proto_binary as pb

        if self.registry is not None and pb.is_framed(payload):
            sid, _indexes, body = pb.unframe(bytes(payload))
            if self._reader_cache is not None and self._reader_cache[0] == sid:
                codec = self._reader_cache[1]
            else:
                reg = self.registry.get_by_id(sid)
                if reg is None:
                    raise SerdeException(f"unknown schema id {sid}")
                codec = pb.codec_for_text(
                    str(reg.schema),
                    tuple(str(r) for r in reg.references if r),
                    self.full_name,
                )
                self._reader_cache = (sid, codec)
            obj = codec.decode(bytes(body))
            upper = {k.upper(): v for k, v in obj.items()}
            out = {c.name: _coerce(upper.get(c.name.upper()), c.type) for c in columns}
            if not self.nullable_all:
                out = {c.name: _proto3_default(out.get(c.name), c.type) for c in columns}
            return self._f32(out)
        out = super().deserialize(payload, columns)
        if out is None:
            return None
        if not self.nullable_all:
            out = {c.name: _proto3_default(out.get(c.name), c.type) for c in columns}
        return self._f32(out)


class ProtobufNoSRFormat(ProtobufFormat):
    """PROTOBUF_NOSR: raw proto3 wire bytes with NO registry and NO framing;
    both sides derive the message from the SQL schema
    (serde/protobuf/ProtobufNoSRFormat.java:29 — the schema travels in the
    query plan, not in SR).  ``binary=True`` selects the wire tier; the
    default stays on the logical JSON tier the in-process topics use."""

    name = "PROTOBUF_NOSR"

    def __init__(self, wrap: bool = True, nullable_all: bool = False,
                 float32: tuple = (), binary: bool = False):
        super().__init__(wrap, nullable_all, float32)
        self.binary = binary
        self._codec_cache: Dict[Any, Any] = {}

    def _codec(self, columns):
        from ksql_tpu.serde import proto_binary as pb

        key = tuple((c.name, str(c.type)) for c in columns)
        codec = self._codec_cache.get(key)
        if codec is None:
            _text, messages = pb.sql_to_proto_schema(
                columns, nullable_all=self.nullable_all
            )
            codec = pb.ProtoCodec(messages, "ConnectDefault1")
            self._codec_cache[key] = codec
        return codec

    def serialize(self, row, columns):
        if row is None:
            return None
        if not self.binary:
            return super().serialize(row, columns)
        value = {c.name: row.get(c.name) for c in columns}
        if not self.nullable_all:
            value = {
                c.name: _proto3_default(value.get(c.name), c.type)
                for c in columns
            }
        return self._codec(columns).encode(value)

    def deserialize(self, payload, columns):
        if self.binary and isinstance(payload, (bytes, bytearray)):
            obj = self._codec(columns).decode(bytes(payload))
            upper = {k.upper(): v for k, v in obj.items()}
            out = {c.name: _coerce(upper.get(c.name.upper()), c.type) for c in columns}
            if not self.nullable_all:
                out = {c.name: _proto3_default(out.get(c.name), c.type) for c in columns}
            return self._f32(out)
        return super().deserialize(payload, columns)


class NoneFormat(Format):
    name = "NONE"

    def serialize(self, row, columns):
        return None

    def deserialize(self, payload, columns):
        return {}


_FORMATS: Dict[str, Any] = {
    "JSON": JsonFormat,
    "JSON_SR": JsonFormat,  # schema'd JSON (SR integration pending)
    "AVRO": AvroFormat,
    "PROTOBUF": ProtobufFormat,
    "PROTOBUF_NOSR": ProtobufNoSRFormat,
    "DELIMITED": DelimitedFormat,
    "KAFKA": KafkaFormat,
    "NONE": NoneFormat,
}


# SerdeFeature support per format (each Format's supportedFeatures:
# json/JsonFormat.java:34, avro/AvroFormat.java:36,
# protobuf/ProtobufFormat.java:35 — PROTOBUF-with-SR is wrap-only)
WRAPPABLE = {"JSON", "JSON_SR", "AVRO", "PROTOBUF", "PROTOBUF_NOSR"}
# WRAP_SINGLE_VALUE=false is also accepted by formats that are inherently
# unwrapped (KAFKA, DELIMITED, NONE): it merely states the status quo
# (SerdeFeaturesFactory) — only =true errors there
UNWRAPPABLE_VALUES = {"JSON", "JSON_SR", "AVRO", "PROTOBUF_NOSR", "KAFKA",
                      "DELIMITED", "NONE"}
# formats where single KEY columns serialize unwrapped
UNWRAPPABLE = {"JSON", "JSON_SR", "AVRO", "PROTOBUF_NOSR", "DELIMITED", "KAFKA", "NONE"}


class _FaultingFormat(Format):
    """Serde-seam fault proxy (wrapped around every ``of()`` result): fires
    the ``serde.serialize`` / ``serde.deserialize`` fault points with the
    format name as context, then delegates.  Corrupt-mode rules mangle the
    payload *before* the real serde sees it, so corruption surfaces as the
    format's own SerdeException."""

    def __init__(self, inner: Format):
        self._inner = inner
        self.name = inner.name

    def serialize(self, row, columns):
        payload = self._inner.serialize(row, columns)
        return faults.fault_point("serde.serialize", self.name, payload)

    def deserialize(self, payload, columns):
        payload = faults.fault_point("serde.deserialize", self.name, payload)
        return self._inner.deserialize(payload, columns)

    def __getattr__(self, attr):  # format-specific surface (wrap, schema, ...)
        return getattr(self._inner, attr)


def of(
    name: str,
    properties: Optional[Dict[str, Any]] = None,
    wrap_single_values: Optional[bool] = None,
    registry=None,
    subject: Optional[str] = None,
) -> Format:
    """FormatFactory.of analog.  Passing a schema ``registry`` (+``subject``)
    to a registry-backed format selects its binary wire tier.  With fault
    injection armed the serde is wrapped in the fault-point proxy (serdes
    are cached per step, so arm faults before queries start)."""
    serde = _of(name, properties, wrap_single_values, registry, subject)
    if faults.armed():
        return _FaultingFormat(serde)
    return serde


def _of(
    name: str,
    properties: Optional[Dict[str, Any]] = None,
    wrap_single_values: Optional[bool] = None,
    registry=None,
    subject: Optional[str] = None,
) -> Format:
    cls = _FORMATS.get(name.upper())
    if cls is None:
        raise SerdeException(f"Unknown format: {name}")
    if cls is DelimitedFormat:
        delim = (properties or {}).get("VALUE_DELIMITER") or ","
        named = {"SPACE": " ", "TAB": "\t"}
        return DelimitedFormat(named.get(str(delim).upper(), str(delim)))
    wrap = wrap_single_values if wrap_single_values is not None else True
    if cls is AvroFormat and registry is not None:
        return AvroFormat(wrap=wrap, registry=registry, subject=subject)
    if cls is ProtobufNoSRFormat:
        p = properties or {}
        return ProtobufNoSRFormat(
            wrap=wrap,
            nullable_all=bool(p.get("PROTO_NULLABLE_ALL", False)),
            float32=tuple(p.get("PROTO_FLOAT32", ()) or ()),
            binary=bool(p.get("PROTO_BINARY", False)),
        )
    if cls is ProtobufFormat:
        p = properties or {}
        return ProtobufFormat(
            wrap=wrap,
            nullable_all=bool(p.get("PROTO_NULLABLE_ALL", False)),
            float32=tuple(p.get("PROTO_FLOAT32", ()) or ()),
            registry=registry,
            subject=subject,
            full_name=p.get("PROTO_FULL_NAME"),
        )
    if issubclass(cls, JsonFormat) and wrap_single_values is not None:
        return cls(wrap=wrap_single_values)
    return cls()


def key_serializer(key_format: str, key_columns, wrapped: bool = False,
                   delimiter: Optional[str] = None) -> Callable[[Tuple[Any, ...]], Any]:
    """The function from a key tuple to its on-topic representation, with
    the format, the columns and the delimiter resolved once.

    Single key columns are unwrapped for every format that supports it
    (SerdeFeaturesFactory.buildKeyFeatures); PROTOBUF stays wrapped.
    DELIMITED keys are CSV text; envelope formats with multiple key columns
    produce a column-name-keyed object.  An empty key is a source record's
    null key payload passed through untouched (Kafka Streams forwards the
    original null key bytes)."""
    cols = list(key_columns)
    if not cols:
        return lambda key: None
    kf = key_format.upper()
    if kf == "DELIMITED":
        named = {"SPACE": " ", "TAB": "\t"}
        d = named.get(str(delimiter).upper(), delimiter) if delimiter else ","
        serde = DelimitedFormat(d)

        def serialize(key):
            if not key or all(v is None for v in key):
                return None
            return serde.serialize({c.name: v for c, v in zip(cols, key)}, cols)

    elif len(cols) == 1 and kf != "PROTOBUF" and not wrapped:
        def serialize(key):
            return key[0] if key else None

    elif kf in ("PROTOBUF", "PROTOBUF_NOSR"):
        def serialize(key):
            if not key or all(v is None for v in key):
                return None  # null key message
            return {c.name: _proto3_default(v, c.type) for c, v in zip(cols, key)}

    else:
        def serialize(key):
            if not key:
                return None
            return {c.name: v for c, v in zip(cols, key)}

    return serialize


def serialize_key(key_format: str, key: Tuple[Any, ...], key_columns,
                  wrapped: bool = False, delimiter: Optional[str] = None) -> Any:
    """Serialize one key tuple (``key_serializer`` applied once)."""
    return key_serializer(key_format, key_columns, wrapped, delimiter)(key)


def deserialize_key(key_format: str, payload: Any, key_columns,
                    delimiter: Optional[str] = None) -> Dict[str, Any]:
    """Inverse of serialize_key: on-topic key -> column dict."""
    cols = list(key_columns)
    if not cols or payload is None:
        return {}
    kf = key_format.upper()
    if isinstance(payload, tuple):
        return {c.name: v for c, v in zip(cols, payload)}
    if isinstance(payload, dict):
        upper = {k.upper(): v for k, v in payload.items()}
        if (
            len(cols) == 1
            and cols[0].type.base == SqlBaseType.STRUCT
            and cols[0].name.upper() not in upper
        ):
            # unwrapped single struct key: the payload IS the struct value
            return {cols[0].name: _coerce(payload, cols[0].type)}
        out = {c.name: _coerce(upper.get(c.name.upper()), c.type) for c in cols}
        if kf in ("PROTOBUF", "PROTOBUF_NOSR"):
            out = {c.name: _proto3_default(out.get(c.name), c.type) for c in cols}
        return out
    if kf == "DELIMITED":
        named = {"SPACE": " ", "TAB": "\t"}
        d = named.get(str(delimiter).upper(), delimiter) if delimiter else ","
        return DelimitedFormat(d).deserialize(payload, cols) or {}
    if len(cols) == 1:
        return {cols[0].name: _coerce(payload, cols[0].type)}
    raise SerdeException(f"cannot deserialize key {payload!r} into {len(cols)} columns")


def supported_formats() -> List[str]:
    return sorted(_FORMATS)


_DELIMITED_TYPES = {
    SqlBaseType.BOOLEAN, SqlBaseType.INTEGER, SqlBaseType.BIGINT,
    SqlBaseType.DOUBLE, SqlBaseType.DECIMAL, SqlBaseType.STRING,
    SqlBaseType.BYTES, SqlBaseType.TIME, SqlBaseType.DATE, SqlBaseType.TIMESTAMP,
}
_KAFKA_TYPES = {
    SqlBaseType.INTEGER, SqlBaseType.BIGINT, SqlBaseType.DOUBLE,
    SqlBaseType.STRING, SqlBaseType.BYTES,
}


AVRO_NAME = __import__("re").compile(r"[A-Za-z_][A-Za-z0-9_]*$")


def _check_map_keys(t: SqlType, fmt: str) -> None:
    if t.base == SqlBaseType.MAP and t.key is not None and t.key.base != SqlBaseType.STRING:
        raise SerdeException(f"{fmt} only supports MAPs with STRING keys")
    for sub in (t.element, t.key):
        if sub is not None:
            _check_map_keys(sub, fmt)
    for _n, ft in t.fields or ():
        _check_map_keys(ft, fmt)


def _check_avro_names(name: str, t: SqlType) -> None:
    if not AVRO_NAME.match(name):
        raise SerdeException(
            f"Schema is not compatible with Avro: Illegal initial character: {name}"
        )
    for fn_, ft in t.fields or ():
        _check_avro_names(fn_, ft)
    if t.element is not None:
        for fn_, ft in t.element.fields or ():
            _check_avro_names(fn_, ft)


def check_schema_support(format_name: str, columns, what: str) -> None:
    """Validate a format can (de)serialize the given columns (the reference's
    Format.supportedFeatures/schema validation, e.g. DelimitedFormat rejects
    nested types and KafkaFormat is single-primitive-only)."""
    f = format_name.upper()
    cols = list(columns)
    if f in ("AVRO", "JSON", "JSON_SR", "PROTOBUF", "PROTOBUF_NOSR"):
        nice = "Avro" if f == "AVRO" else f
        for c in cols:
            _check_map_keys(c.type, nice)
    if f == "AVRO":
        for c in cols:
            _check_avro_names(c.name, c.type)
    if f == "DELIMITED":
        for c in cols:
            if c.type.base not in _DELIMITED_TYPES:
                raise SerdeException(
                    f"The 'DELIMITED' format does not support type '{c.type.base.value}', "
                    f"column: `{c.name}`"
                )
    if f == "KAFKA":
        if len(cols) > 1:
            schema_desc = ", ".join(f"`{c.name}` {c.type} KEY" for c in cols)
            raise SerdeException(
                ("Key format does not support schema.\nformat: KAFKA\n"
                 f"schema: Persistence{{columns=[{schema_desc}], features=[]}}\n"
                 "reason: The 'KAFKA' format only supports a single field. Got: "
                 if what == "key" else
                 "The 'KAFKA' format only supports a single field. Got: ")
                + str([f"`{c.name}` {c.type}" for c in cols])
            )
        for c in cols:
            if c.type.base not in _KAFKA_TYPES:
                raise SerdeException(
                    f"The 'KAFKA' format does not support type '{c.type.base.value}', "
                    f"column: `{c.name}`"
                )
    if f == "NONE" and what == "value" and cols:
        raise SerdeException(
            "The 'NONE' format can only be used when no columns are defined."
        )


def contains_map(t: SqlType) -> bool:
    if t.base == SqlBaseType.MAP:
        return True
    if t.element is not None and contains_map(t.element):
        return True
    if t.key is not None and contains_map(t.key):
        return True
    for _, ft in t.fields or ():
        if contains_map(ft):
            return True
    return False
