// Native batch ingest: record payloads -> columnar arrays.
//
// The C++ tier of the host ingest pipeline (SURVEY §2.2: the reference's
// native dependencies are RocksDB + Kafka client codecs; our equivalent is
// a columnar decoder feeding the device DMA path).  One call parses a
// whole micro-batch of payloads into fixed-width column arrays
// (numeric/boolean) and stable-hash64 codes (strings), bypassing per-record
// Python dict materialization entirely.  Three payload modes share the
// call (MODE_* below): wrapped JSON objects, unwrapped single JSON scalars
// (SerdeFeature UNWRAP_SINGLES), and DELIMITED (commons-csv minimal-quote)
// rows.  A payload the native grammar cannot take bit-identically to the
// Python serde marks its row not-ok and the caller replays it per record.
//
// Hash compatibility: string codes must be bit-identical to
// ksql_tpu/common/batch.py:stable_hash64 — blake2b(digest_size=8) over
// b"\x00" + utf8, little-endian signed.  The BLAKE2b core below follows
// RFC 7693.
//
// Build (native/__init__.py does it on first use, no deps):
//   g++ -O3 -shared -fPIC -std=c++17 ingest.cc -o _libingest.<sha256 of this file>.so

#include <cstdint>
#include <cstring>
#include <cstdlib>
#include <cmath>
#include <string>
#include <unordered_map>
#include <vector>

// ------------------------------------------------------------------ blake2b

namespace {

static const uint64_t blake2b_IV[8] = {
    0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL, 0x3c6ef372fe94f82bULL,
    0xa54ff53a5f1d36f1ULL, 0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
    0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL};

static const uint8_t blake2b_sigma[12][16] = {
    {0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15},
    {14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3},
    {11, 8, 12, 0, 5, 2, 15, 13, 10, 14, 3, 6, 7, 1, 9, 4},
    {7, 9, 3, 1, 13, 12, 11, 14, 2, 6, 5, 10, 4, 0, 15, 8},
    {9, 0, 5, 7, 2, 4, 10, 15, 14, 1, 11, 12, 6, 8, 3, 13},
    {2, 12, 6, 10, 0, 11, 8, 3, 4, 13, 7, 5, 15, 14, 1, 9},
    {12, 5, 1, 15, 14, 13, 4, 10, 0, 7, 6, 3, 9, 2, 8, 11},
    {13, 11, 7, 14, 12, 1, 3, 9, 5, 0, 15, 4, 8, 6, 2, 10},
    {6, 15, 14, 9, 11, 3, 0, 8, 12, 2, 13, 7, 1, 4, 10, 5},
    {10, 2, 8, 4, 7, 6, 1, 5, 15, 11, 9, 14, 3, 12, 13, 0},
    {0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15},
    {14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3}};

static inline uint64_t rotr64(uint64_t x, int n) {
  return (x >> n) | (x << (64 - n));
}

struct Blake2bState {
  uint64_t h[8];
  uint64_t t[2];
  uint8_t buf[128];
  size_t buflen;
};

static void blake2b_compress(Blake2bState* S, const uint8_t block[128],
                             int last) {
  uint64_t m[16], v[16];
  for (int i = 0; i < 16; i++) {
    memcpy(&m[i], block + i * 8, 8);
  }
  for (int i = 0; i < 8; i++) v[i] = S->h[i];
  for (int i = 0; i < 8; i++) v[i + 8] = blake2b_IV[i];
  v[12] ^= S->t[0];
  v[13] ^= S->t[1];
  if (last) v[14] = ~v[14];
#define G(r, i, a, b, c, d)                      \
  do {                                           \
    a = a + b + m[blake2b_sigma[r][2 * i]];      \
    d = rotr64(d ^ a, 32);                       \
    c = c + d;                                   \
    b = rotr64(b ^ c, 24);                       \
    a = a + b + m[blake2b_sigma[r][2 * i + 1]];  \
    d = rotr64(d ^ a, 16);                       \
    c = c + d;                                   \
    b = rotr64(b ^ c, 63);                       \
  } while (0)
  for (int r = 0; r < 12; r++) {
    G(r, 0, v[0], v[4], v[8], v[12]);
    G(r, 1, v[1], v[5], v[9], v[13]);
    G(r, 2, v[2], v[6], v[10], v[14]);
    G(r, 3, v[3], v[7], v[11], v[15]);
    G(r, 4, v[0], v[5], v[10], v[15]);
    G(r, 5, v[1], v[6], v[11], v[12]);
    G(r, 6, v[2], v[7], v[8], v[13]);
    G(r, 7, v[3], v[4], v[9], v[14]);
  }
#undef G
  for (int i = 0; i < 8; i++) S->h[i] ^= v[i] ^ v[i + 8];
}

// blake2b with digest_size=8, no key (hashlib.blake2b(raw, digest_size=8))
static int64_t blake2b8(const uint8_t* data, size_t len) {
  Blake2bState S;
  memset(&S, 0, sizeof(S));
  for (int i = 0; i < 8; i++) S.h[i] = blake2b_IV[i];
  // parameter block: digest_length=8, fanout=1, depth=1
  S.h[0] ^= 0x01010008ULL;
  while (len > 128) {
    S.t[0] += 128;
    blake2b_compress(&S, data, 0);
    data += 128;
    len -= 128;
  }
  uint8_t block[128];
  memset(block, 0, 128);
  memcpy(block, data, len);
  S.t[0] += len;
  blake2b_compress(&S, block, 1);
  int64_t out;
  memcpy(&out, &S.h[0], 8);  // little-endian digest prefix
  return out;
}

// stable_hash64 of a string value: blake2b8 over b"\x00" + utf8
static int64_t hash_string(const char* s, size_t len) {
  std::vector<uint8_t> raw(len + 1);
  raw[0] = 0x00;
  memcpy(raw.data() + 1, s, len);
  return blake2b8(raw.data(), raw.size());
}

// ------------------------------------------------------------- JSON parser

struct Cursor {
  const char* p;
  const char* end;
};

static inline void skip_ws(Cursor* c) {
  while (c->p < c->end &&
         (*c->p == ' ' || *c->p == '\t' || *c->p == '\n' || *c->p == '\r'))
    c->p++;
}

// decode a JSON string starting at the opening quote into out (UTF-8);
// returns 0 on failure; cursor ends after closing quote
static int parse_string(Cursor* c, std::string* out) {
  if (c->p >= c->end || *c->p != '"') return 0;
  c->p++;
  out->clear();
  while (c->p < c->end) {
    char ch = *c->p;
    if (ch == '"') {
      c->p++;
      return 1;
    }
    if (ch == '\\') {
      c->p++;
      if (c->p >= c->end) return 0;
      char e = *c->p++;
      switch (e) {
        case '"': out->push_back('"'); break;
        case '\\': out->push_back('\\'); break;
        case '/': out->push_back('/'); break;
        case 'b': out->push_back('\b'); break;
        case 'f': out->push_back('\f'); break;
        case 'n': out->push_back('\n'); break;
        case 'r': out->push_back('\r'); break;
        case 't': out->push_back('\t'); break;
        case 'u': {
          if (c->end - c->p < 4) return 0;
          unsigned cp = 0;
          for (int i = 0; i < 4; i++) {
            char h = c->p[i];
            cp <<= 4;
            if (h >= '0' && h <= '9') cp |= h - '0';
            else if (h >= 'a' && h <= 'f') cp |= h - 'a' + 10;
            else if (h >= 'A' && h <= 'F') cp |= h - 'A' + 10;
            else return 0;
          }
          c->p += 4;
          // surrogate pair
          if (cp >= 0xD800 && cp <= 0xDBFF && c->end - c->p >= 6 &&
              c->p[0] == '\\' && c->p[1] == 'u') {
            unsigned lo = 0;
            for (int i = 0; i < 4; i++) {
              char h = c->p[2 + i];
              lo <<= 4;
              if (h >= '0' && h <= '9') lo |= h - '0';
              else if (h >= 'a' && h <= 'f') lo |= h - 'a' + 10;
              else if (h >= 'A' && h <= 'F') lo |= h - 'A' + 10;
              else return 0;
            }
            if (lo >= 0xDC00 && lo <= 0xDFFF) {
              cp = 0x10000 + ((cp - 0xD800) << 10) + (lo - 0xDC00);
              c->p += 6;
            }
          }
          // UTF-8 encode
          if (cp < 0x80) {
            out->push_back((char)cp);
          } else if (cp < 0x800) {
            out->push_back((char)(0xC0 | (cp >> 6)));
            out->push_back((char)(0x80 | (cp & 0x3F)));
          } else if (cp < 0x10000) {
            out->push_back((char)(0xE0 | (cp >> 12)));
            out->push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
            out->push_back((char)(0x80 | (cp & 0x3F)));
          } else {
            out->push_back((char)(0xF0 | (cp >> 18)));
            out->push_back((char)(0x80 | ((cp >> 12) & 0x3F)));
            out->push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
            out->push_back((char)(0x80 | (cp & 0x3F)));
          }
          break;
        }
        default: return 0;
      }
      continue;
    }
    if ((unsigned char)ch < 0x20) return 0;  // json.loads strict mode
    out->push_back(ch);
    c->p++;
  }
  return 0;
}

// skip any JSON value (for fields we don't extract); returns 0 on failure
static int skip_value(Cursor* c) {
  skip_ws(c);
  if (c->p >= c->end) return 0;
  char ch = *c->p;
  if (ch == '"') {
    std::string tmp;
    return parse_string(c, &tmp);
  }
  if (ch == '{' || ch == '[') {
    char open = ch, close = (ch == '{') ? '}' : ']';
    int depth = 0;
    while (c->p < c->end) {
      char x = *c->p;
      if (x == '"') {
        std::string tmp;
        if (!parse_string(c, &tmp)) return 0;
        continue;
      }
      if (x == open) depth++;
      if (x == close) {
        depth--;
        if (depth == 0) {
          c->p++;
          return 1;
        }
      }
      c->p++;
    }
    return 0;
  }
  // literal / number: scan to delimiter
  while (c->p < c->end && *c->p != ',' && *c->p != '}' && *c->p != ']' &&
         *c->p != ' ' && *c->p != '\t' && *c->p != '\n' && *c->p != '\r')
    c->p++;
  return 1;
}

// field type codes (mirror ksql_tpu/native/__init__.py)
enum FieldType {
  FT_BIGINT = 0,   // int64
  FT_INT = 1,      // int32
  FT_DOUBLE = 2,   // float64
  FT_BOOLEAN = 3,  // uint8
  FT_STRING = 4,   // int64 stable-hash codes
};

struct StringArena {
  // unique strings discovered this batch (for host dictionary learning)
  std::unordered_map<int64_t, uint32_t> seen;  // hash -> index
  std::string bytes;                           // concatenated utf-8
  std::vector<int64_t> offsets;                // per-unique end offset
  std::vector<int64_t> hashes;
};

// payload modes (mirror ksql_tpu/native/__init__.py)
enum ParseMode {
  MODE_JSON_WRAPPED = 0,    // one JSON object per payload
  MODE_JSON_UNWRAPPED = 1,  // one bare JSON scalar per payload (nf == 1)
  MODE_DELIMITED = 2,       // commons-csv minimal-quote row per payload
};

// shared per-batch parse context: output columns + string scratch
struct ParseCtx {
  int nf;
  const int32_t* types;
  void** out_data;
  uint8_t** out_valid;
  StringArena* arena;
  std::vector<std::string> fnames;
  std::string key, sval;              // scratch (object / single modes)
  std::vector<std::string> fields;    // scratch (delimited mode)
};

static void store_string(ParseCtx* x, int fi, int i, const std::string& s) {
  int64_t h = hash_string(s.data(), s.size());
  ((int64_t*)x->out_data[fi])[i] = h;
  x->out_valid[fi][i] = 1;
  if (x->arena && x->arena->seen.find(h) == x->arena->seen.end()) {
    x->arena->seen.emplace(h, (uint32_t)x->arena->hashes.size());
    x->arena->bytes.append(s);
    x->arena->offsets.push_back((int64_t)x->arena->bytes.size());
    x->arena->hashes.push_back(h);
  }
}

// strict JSON number grammar at the cursor (strtod alone would accept
// hex/inf/nan and fabricate values Python rejects).  On success advances
// the cursor past the token and returns 1 with [*tok_s, *tok_e) set;
// *integral is false when a fraction or exponent appeared.  The character
// after the token is NOT validated here — callers check their own
// delimiter/end expectations.
static int scan_json_number(Cursor* c, bool* integral, const char** tok_s,
                            const char** tok_e) {
  const char* start = c->p;
  const char* q = start;
  if (q < c->end && *q == '-') q++;
  const char* digs = q;
  while (q < c->end && *q >= '0' && *q <= '9') q++;
  *integral = true;
  // JSON forbids leading zeros ("01"); Python json drops the record
  bool grammar_ok = q > digs && !(*digs == '0' && q - digs > 1);
  if (q < c->end && *q == '.') {
    *integral = false;
    q++;
    const char* fr = q;
    while (q < c->end && *q >= '0' && *q <= '9') q++;
    grammar_ok = grammar_ok && q > fr;
  }
  if (grammar_ok && q < c->end && (*q == 'e' || *q == 'E')) {
    *integral = false;
    q++;
    if (q < c->end && (*q == '+' || *q == '-')) q++;
    const char* ex = q;
    while (q < c->end && *q >= '0' && *q <= '9') q++;
    grammar_ok = grammar_ok && q > ex;
  }
  if (!grammar_ok) return 0;
  *tok_s = start;
  *tok_e = q;
  c->p = q;
  return 1;
}

// store a validated JSON number token into a numeric column; returns 0
// when Python-fallback semantics apply (fractional into int, overflow)
static int store_number(ParseCtx* x, int fi, int i, const char* s,
                        const char* e, bool integral) {
  std::string tok(s, e - s);
  if (x->types[fi] == FT_DOUBLE) {
    ((double*)x->out_data[fi])[i] = strtod(tok.c_str(), nullptr);
    x->out_valid[fi][i] = 1;
    return 1;
  }
  if (!integral) return 0;  // fractional into an int column: Python semantics
  errno = 0;
  long long v = strtoll(tok.c_str(), nullptr, 10);
  if (errno == ERANGE) return 0;
  if (x->types[fi] == FT_BIGINT) {
    ((int64_t*)x->out_data[fi])[i] = (int64_t)v;
  } else {
    if (v < INT32_MIN || v > INT32_MAX) return 0;
    ((int32_t*)x->out_data[fi])[i] = (int32_t)v;
  }
  x->out_valid[fi][i] = 1;
  return 1;
}

// ---------------------------------------------------- mode 0: JSON object

static int parse_row_object(ParseCtx* x, Cursor c, int i) {
  skip_ws(&c);
  if (c.p >= c.end || *c.p != '{') return 0;
  c.p++;
  int ok = 1;
  while (ok) {
    skip_ws(&c);
    if (c.p < c.end && *c.p == '}') {
      c.p++;
      break;
    }
    if (!parse_string(&c, &x->key)) {
      ok = 0;
      break;
    }
    skip_ws(&c);
    if (c.p >= c.end || *c.p != ':') {
      ok = 0;
      break;
    }
    c.p++;
    skip_ws(&c);
    // exact field-name match, else case-insensitive
    int fi = -1;
    for (int f = 0; f < x->nf; f++) {
      if (x->fnames[f] == x->key) {
        fi = f;
        break;
      }
    }
    if (fi < 0) {
      for (int f = 0; f < x->nf; f++) {
        if (x->fnames[f].size() == x->key.size()) {
          bool eq = true;
          for (size_t j = 0; j < x->key.size(); j++) {
            char a = x->fnames[f][j], b = x->key[j];
            if (a >= 'a' && a <= 'z') a -= 32;
            if (b >= 'a' && b <= 'z') b -= 32;
            if (a != b) { eq = false; break; }
          }
          if (eq) { fi = f; break; }
        }
      }
    }
    if (fi < 0) {
      // Unmatched key with non-ASCII bytes: full-Unicode case folding
      // (the Python path's str.upper()) might still match it to a
      // field, so let the Python fallback decide the whole row.
      for (size_t j = 0; j < x->key.size(); j++) {
        if ((unsigned char)x->key[j] >= 0x80) { ok = 0; break; }
      }
      if (!ok) break;
      if (!skip_value(&c)) ok = 0;
    } else {
      char ch = (c.p < c.end) ? *c.p : 0;
      if (ch == 'n' && c.end - c.p >= 4 && !memcmp(c.p, "null", 4)) {
        c.p += 4;  // null -> invalid; clears an earlier duplicate key's
        x->out_valid[fi][i] = 0;  // value (Python dict semantics: last wins)
      } else if (x->types[fi] == FT_STRING) {
        if (ch == '"') {
          if (!parse_string(&c, &x->sval)) { ok = 0; break; }
          store_string(x, fi, i, x->sval);
        } else {
          ok = 0;  // non-string value for a string field: Python decides
        }
      } else if (x->types[fi] == FT_BOOLEAN) {
        if (ch == 't' && c.end - c.p >= 4 && !memcmp(c.p, "true", 4)) {
          c.p += 4;
          ((uint8_t*)x->out_data[fi])[i] = 1;
          x->out_valid[fi][i] = 1;
        } else if (ch == 'f' && c.end - c.p >= 5 && !memcmp(c.p, "false", 5)) {
          c.p += 5;
          ((uint8_t*)x->out_data[fi])[i] = 0;
          x->out_valid[fi][i] = 1;
        } else {
          ok = 0;
        }
      } else {
        bool integral;
        const char* ts;
        const char* te;
        if (!scan_json_number(&c, &integral, &ts, &te) ||
            (c.p < c.end && *c.p != ',' && *c.p != '}' && *c.p != ']' &&
             *c.p != ' ' && *c.p != '\t' && *c.p != '\n' && *c.p != '\r')) {
          ok = 0;
        } else if (!store_number(x, fi, i, ts, te, integral)) {
          ok = 0;
          continue;
        }
      }
    }
    if (!ok) break;
    skip_ws(&c);
    if (c.p < c.end && *c.p == ',') {
      c.p++;
      continue;
    }
    if (c.p < c.end && *c.p == '}') {
      c.p++;
      break;
    }
    ok = 0;
  }
  if (!ok) return 0;
  skip_ws(&c);
  return c.p == c.end ? 1 : 0;
}

// ------------------------------------------- mode 1: unwrapped JSON scalar
//
// One bare JSON value per payload into the single requested column,
// mirroring JsonFormat(wrap=False) + _coerce.  Cross-type coercions the
// Python serde applies (string->int, number->str, bool()->truthiness, ...)
// defer to the fallback; a payload json.loads would reject lands a single
// STRING column as raw text (JsonFormat's unwrapped raw-text path).
static int parse_row_single(ParseCtx* x, Cursor c, int i) {
  const char* raw_s = c.p;
  const char* raw_e = c.end;
  int32_t t = x->types[0];
  skip_ws(&c);
  if (c.p >= c.end) {
    // whitespace-only payload: json.loads raises -> raw text for STRING
    if (t != FT_STRING) return 0;
    x->sval.assign(raw_s, raw_e - raw_s);
    store_string(x, 0, i, x->sval);
    return 1;
  }
  char ch = *c.p;
  if (ch == '"') {
    if (parse_string(&c, &x->sval)) {
      skip_ws(&c);
      if (c.p == c.end) {
        if (t != FT_STRING) return 0;  // string into numeric/bool: Python
        store_string(x, 0, i, x->sval);
        return 1;
      }
    }
    // bad string / trailing garbage: json.loads fails on both
    if (t != FT_STRING) return 0;
    x->sval.assign(raw_s, raw_e - raw_s);
    store_string(x, 0, i, x->sval);
    return 1;
  }
  if (ch == 'n' && c.end - c.p >= 4 && !memcmp(c.p, "null", 4)) {
    Cursor after{c.p + 4, c.end};
    skip_ws(&after);
    if (after.p == after.end) return 1;  // null -> NULL (valid stays 0)
    // "null..." trailing garbage: invalid JSON
    if (t != FT_STRING) return 0;
    x->sval.assign(raw_s, raw_e - raw_s);
    store_string(x, 0, i, x->sval);
    return 1;
  }
  if (ch == 't' || ch == 'f') {
    int len = ch == 't' ? 4 : 5;
    const char* lit = ch == 't' ? "true" : "false";
    if (c.end - c.p >= len && !memcmp(c.p, lit, len)) {
      Cursor after{c.p + len, c.end};
      skip_ws(&after);
      if (after.p == after.end) {
        if (t != FT_BOOLEAN) return 0;  // bool coercion: Python decides
        ((uint8_t*)x->out_data[0])[i] = ch == 't' ? 1 : 0;
        x->out_valid[0][i] = 1;
        return 1;
      }
    }
    // not the literal: invalid JSON -> raw text for STRING
    if (t != FT_STRING) return 0;
    x->sval.assign(raw_s, raw_e - raw_s);
    store_string(x, 0, i, x->sval);
    return 1;
  }
  if (ch == '{' || ch == '[') return 0;  // composite: Python decides
  if (ch == 'I' || ch == 'N' || (ch == '-' && c.end - c.p >= 2 &&
                                 c.p[1] == 'I')) {
    // Python's json accepts Infinity/-Infinity/NaN constants: defer
    return 0;
  }
  if (ch == '-' || (ch >= '0' && ch <= '9')) {
    bool integral;
    const char* ts;
    const char* te;
    if (scan_json_number(&c, &integral, &ts, &te)) {
      skip_ws(&c);
      if (c.p == c.end) {
        if (t == FT_STRING || t == FT_BOOLEAN) return 0;  // coercion: Python
        return store_number(x, 0, i, ts, te, integral);
      }
    }
    // invalid number / trailing garbage: invalid JSON
    if (t != FT_STRING) return 0;
    x->sval.assign(raw_s, raw_e - raw_s);
    store_string(x, 0, i, x->sval);
    return 1;
  }
  // anything else cannot start a JSON value: raw text for STRING
  if (t != FT_STRING) return 0;
  x->sval.assign(raw_s, raw_e - raw_s);
  store_string(x, 0, i, x->sval);
  return 1;
}

// ------------------------------------------------------ mode 2: DELIMITED

// DelimitedFormat._split bit-exactly: stateful quote-aware scan with
// doubled-quote escapes; a split never fails (unterminated quotes just
// consume to end-of-payload, like the Python parser)
static void delim_split(const char* p, const char* end, char delim,
                        std::vector<std::string>* out) {
  out->clear();
  std::string cur;
  bool in_quotes = false;
  while (p < end) {
    char ch = *p;
    if (in_quotes) {
      if (ch == '"') {
        if (p + 1 < end && p[1] == '"') {
          cur.push_back('"');
          p += 2;
          continue;
        }
        in_quotes = false;
      } else {
        cur.push_back(ch);
      }
    } else if (ch == '"') {
      in_quotes = true;
    } else if (ch == delim) {
      out->push_back(cur);
      cur.clear();
    } else {
      cur.push_back(ch);
    }
    p++;
  }
  out->push_back(cur);
}

static bool all_ascii(const std::string& s) {
  for (char ch : s) {
    if ((unsigned char)ch >= 0x80) return false;
  }
  return true;
}

// the ASCII whitespace int()/float() accept around a numeric literal
static inline bool ascii_ws(char ch) {
  return ch == ' ' || ch == '\t' || ch == '\n' || ch == '\r' || ch == '\v' ||
         ch == '\f';
}

// str.strip()'s ASCII whitespace is wider: \x1c-\x1f are Unicode
// whitespace (separator controls) that int()/float() reject
static inline bool strip_ws(char ch) {
  return ascii_ws(ch) || ((unsigned char)ch >= 0x1c && (unsigned char)ch <= 0x1f);
}

// Python int(raw): optional surrounding whitespace, [+-]?digits.  The
// grammar here is strictly narrower (no underscores, no unicode digits) —
// anything else defers to the fallback, which reproduces int()'s full
// behavior including its ValueError.
static int parse_delim_int(const std::string& s, long long* out) {
  size_t a = 0, b = s.size();
  while (a < b && ascii_ws(s[a])) a++;
  while (b > a && ascii_ws(s[b - 1])) b--;
  if (a >= b) return 0;
  size_t q = a;
  if (s[q] == '+' || s[q] == '-') q++;
  size_t digs = q;
  while (q < b && s[q] >= '0' && s[q] <= '9') q++;
  if (q != b || q == digs) return 0;
  std::string tok(s, a, b - a);
  errno = 0;
  long long v = strtoll(tok.c_str(), nullptr, 10);
  if (errno == ERANGE) return 0;
  *out = v;
  return 1;
}

// Python float(raw) over the plain-decimal grammar ("1.", ".5", "1e3");
// inf/nan/underscored literals defer to the fallback
static int parse_delim_double(const std::string& s, double* out) {
  size_t a = 0, b = s.size();
  while (a < b && ascii_ws(s[a])) a++;
  while (b > a && ascii_ws(s[b - 1])) b--;
  if (a >= b) return 0;
  size_t q = a;
  if (s[q] == '+' || s[q] == '-') q++;
  size_t int_digs = 0, frac_digs = 0;
  while (q < b && s[q] >= '0' && s[q] <= '9') { q++; int_digs++; }
  if (q < b && s[q] == '.') {
    q++;
    while (q < b && s[q] >= '0' && s[q] <= '9') { q++; frac_digs++; }
  }
  if (int_digs + frac_digs == 0) return 0;
  if (q < b && (s[q] == 'e' || s[q] == 'E')) {
    q++;
    if (q < b && (s[q] == '+' || s[q] == '-')) q++;
    size_t ex = q;
    while (q < b && s[q] >= '0' && s[q] <= '9') q++;
    if (q == ex) return 0;
  }
  if (q != b) return 0;
  std::string tok(s, a, b - a);
  *out = strtod(tok.c_str(), nullptr);
  return 1;
}

static int parse_row_delimited(ParseCtx* x, Cursor c, int i, char delim) {
  delim_split(c.p, c.end, delim, &x->fields);
  if ((int)x->fields.size() != x->nf) {
    return 0;  // count mismatch: Python raises SerdeException (error-logged)
  }
  for (int f = 0; f < x->nf; f++) {
    const std::string& raw = x->fields[f];
    if (raw.empty()) continue;  // "" -> NULL (valid stays 0)
    switch (x->types[f]) {
      case FT_STRING:
        store_string(x, f, i, raw);
        break;
      case FT_BOOLEAN: {
        // raw.strip().lower() == "true"; non-ASCII bytes could be unicode
        // whitespace under Python's strip -> defer
        if (!all_ascii(raw)) return 0;
        size_t a = 0, b = raw.size();
        while (a < b && strip_ws(raw[a])) a++;
        while (b > a && strip_ws(raw[b - 1])) b--;
        bool t = (b - a) == 4;
        static const char* lit = "true";
        for (size_t j = 0; t && j < 4; j++) {
          char ch = raw[a + j];
          if (ch >= 'A' && ch <= 'Z') ch += 32;
          if (ch != lit[j]) t = false;
        }
        ((uint8_t*)x->out_data[f])[i] = t ? 1 : 0;
        x->out_valid[f][i] = 1;
        break;
      }
      case FT_DOUBLE: {
        if (!all_ascii(raw)) return 0;
        double v;
        if (!parse_delim_double(raw, &v)) return 0;
        ((double*)x->out_data[f])[i] = v;
        x->out_valid[f][i] = 1;
        break;
      }
      default: {  // FT_BIGINT / FT_INT
        if (!all_ascii(raw)) return 0;
        long long v;
        if (!parse_delim_int(raw, &v)) return 0;
        if (x->types[f] == FT_BIGINT) {
          ((int64_t*)x->out_data[f])[i] = (int64_t)v;
        } else {
          if (v < INT32_MIN || v > INT32_MAX) return 0;
          ((int32_t*)x->out_data[f])[i] = (int32_t)v;
        }
        x->out_valid[f][i] = 1;
        break;
      }
    }
  }
  return 1;
}

}  // namespace

extern "C" {

// Parse n payloads into columns.
//
//   buf/offsets: payload i is buf[offsets[i] .. offsets[i+1])
//   nf fields: names (concatenated, name_offsets), types[nf]
//   out_data[f]: int64*/int32*/double*/uint8* per type, length n
//   out_valid[f]: uint8* length n
//   row_ok: uint8* length n — 0 where the payload failed to parse (caller
//           falls back to the Python decoder for those rows)
//   mode: ParseMode; delim: field separator for MODE_DELIMITED
//
// Returns an opaque StringArena* holding this batch's unique strings (fetch
// with ingest_arena_*; free with ingest_free_arena), or nullptr when no
// string fields were requested.
void* ingest_parse_batch2(const char* buf, const int64_t* offsets, int n,
                          int nf, const char* names,
                          const int64_t* name_offsets, const int32_t* types,
                          void** out_data, uint8_t** out_valid,
                          uint8_t* row_ok, int32_t mode, char delim) {
  ParseCtx x;
  x.nf = nf;
  x.types = types;
  x.out_data = out_data;
  x.out_valid = out_valid;
  x.arena = nullptr;
  for (int f = 0; f < nf; f++) {
    if (types[f] == FT_STRING && x.arena == nullptr) {
      x.arena = new StringArena();
    }
  }
  x.fnames.resize(nf);
  for (int f = 0; f < nf; f++) {
    x.fnames[f].assign(names + name_offsets[f], names + name_offsets[f + 1]);
  }
  for (int i = 0; i < n; i++) {
    for (int f = 0; f < nf; f++) out_valid[f][i] = 0;
    Cursor c{buf + offsets[i], buf + offsets[i + 1]};
    int ok;
    switch (mode) {
      case MODE_JSON_UNWRAPPED:
        ok = parse_row_single(&x, c, i);
        break;
      case MODE_DELIMITED:
        ok = parse_row_delimited(&x, c, i, delim);
        break;
      default:
        ok = parse_row_object(&x, c, i);
        break;
    }
    row_ok[i] = ok ? 1 : 0;
    if (!ok) {
      for (int f = 0; f < nf; f++) out_valid[f][i] = 0;
    }
  }
  return x.arena;
}

// legacy entry: wrapped-JSON objects only
void* ingest_parse_batch(const char* buf, const int64_t* offsets, int n,
                         int nf, const char* names, const int64_t* name_offsets,
                         const int32_t* types, void** out_data,
                         uint8_t** out_valid, uint8_t* row_ok) {
  return ingest_parse_batch2(buf, offsets, n, nf, names, name_offsets, types,
                             out_data, out_valid, row_ok, MODE_JSON_WRAPPED,
                             ',');
}

int64_t ingest_arena_count(void* arena) {
  return arena ? (int64_t)((StringArena*)arena)->hashes.size() : 0;
}

int64_t ingest_arena_bytes_len(void* arena) {
  return arena ? (int64_t)((StringArena*)arena)->bytes.size() : 0;
}

void ingest_arena_fetch(void* arena, int64_t* hashes, int64_t* ends,
                        char* bytes) {
  if (!arena) return;
  StringArena* a = (StringArena*)arena;
  memcpy(hashes, a->hashes.data(), a->hashes.size() * 8);
  memcpy(ends, a->offsets.data(), a->offsets.size() * 8);
  memcpy(bytes, a->bytes.data(), a->bytes.size());
}

void ingest_free_arena(void* arena) {
  delete (StringArena*)arena;
}

int64_t ingest_hash_string(const char* s, int64_t len) {
  return hash_string(s, (size_t)len);
}

}  // extern "C"
